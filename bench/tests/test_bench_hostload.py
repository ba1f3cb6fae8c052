"""The host-load readings logged beside a run are shares of one window."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import hostload  # noqa: E402


def test_readings_over_a_window_are_shares():
    before = hostload.snapshot()
    sum(i * i for i in range(200_000))
    got = hostload.since(before)
    assert set(got) == {"steal_share", "busy_share", "involuntary_switches"}
    for key in ("steal_share", "busy_share"):
        assert got[key] is None or 0.0 <= got[key] <= 1.0
    assert got["involuntary_switches"] is None or \
        got["involuntary_switches"] >= 0


def test_no_proc_reads_none(monkeypatch):
    monkeypatch.setattr(hostload, "_cpu", lambda: None)
    monkeypatch.setattr(hostload, "_switches", lambda: None)
    assert hostload.since(hostload.snapshot()) == {
        "steal_share": None, "busy_share": None,
        "involuntary_switches": None}
