"""The trace reduction on small hand-worked timelines and on one decode
tick recorded on a TPU v5e: busy union, idle share, kernel time by the
kernel's source, self time of nested operations, device time inside host
spans, and idle gaps labelled by the host span open at the time."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
FIXTURE = os.path.join(BENCH, "tests", "fixture")
PAGED = "repro/kernels/paged_decode.py:285"


def _raw():
    # window 0-100 ms; ops overlap at 10-30 (fusion + kernel), 50-60
    ops = [[10 * MS, 25 * MS, "fusion.12", "", ""],
           [20 * MS, 30 * MS, "custom-call.4", PAGED, "jit(f)/pallas_call:"],
           [50 * MS, 60 * MS, "fusion.3", "", ""],
           [95 * MS, 120 * MS, "fusion.7", "", ""]]
    spans = [[0, 100 * MS, "bench_window"],
             [0, 45 * MS, "serve_tick"],
             [5 * MS, 35 * MS, "decode_dispatch"],
             [35 * MS, 45 * MS, "sample_emit"],
             [45 * MS, 90 * MS, "serve_tick"],
             [46 * MS, 62 * MS, "prefill"]]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_interval_arithmetic():
    assert tr.union([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    assert tr.clip([[0, 5], [8, 12]], 2, 10) == [[2, 5], [8, 10]]
    assert tr.overlap([[0, 5], [8, 12]], [[4, 9]]) == 2
    assert tr.gaps([[2, 3], [5, 8]], 0, 10) == [[0, 2], [3, 5], [8, 10]]
    assert tr.op_group("fusion.12") == "fusion"
    assert tr.op_group("copy_start.3.1") == "copy_start"
    # a while (0-10) holding two operations (1-3, 4-8), then one alone
    assert tr.self_times([[0, 10], [1, 3], [4, 8], [12, 13]]) == [4, 2, 4, 1]


def test_kernel_source_names_only_pallas_kernels():
    call = {"long_name": 'custom-call(), custom_call_target="tpu_custom_call"',
            "source": "/x/src/repro/kernels/paged_decode.py:285"}
    assert tr.kernel_source(call) == PAGED
    assert tr.kernel_source({"long_name": "fusion(...)",
                             "source": "/x/src/repro/serve/decode.py:3"}) == ""


def test_busy_idle_and_kernels():
    t = tr.Trace(_raw())
    assert t.window_s == pytest.approx(0.1)
    # busy: 10-30, 50-60, 95-100 (clipped) = 35 ms
    assert t.busy_s == pytest.approx(0.035)
    assert t.idle_share == pytest.approx(0.65)
    assert t.kernel_seconds(("kernels/paged_decode.py",)) == \
        pytest.approx(0.010)
    assert t.kernel_seconds(("kernels/ss_attention.py",)) == 0.0
    assert t.kernel_seconds() == pytest.approx(0.010)
    assert t.kernel_seconds(exclude=("kernels/paged_decode.py",)) == 0.0
    assert t.kernel_table() == {PAGED: pytest.approx(0.010)}
    # device busy inside the prefill span (46-62): 50-60
    assert t.busy_within(t.span_intervals("prefill")) == pytest.approx(0.010)


def test_idle_gaps_are_labelled_by_the_open_host_span():
    t = tr.Trace(_raw())
    assert t.idle_gaps() == [[0, 10 * MS], [30 * MS, 50 * MS],
                             [60 * MS, 95 * MS]]
    assert t.labels([2 * MS, 5 * MS, 40 * MS, 77 * MS, 99 * MS]) == [
        "serve_tick", "decode_dispatch", "sample_emit", "serve_tick", "idle"]
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(0.025)]
    assert b["device_ops"][1] == ["kernel " + PAGED, pytest.approx(0.010)]
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"decode_dispatch": 0.010, "sample_emit": 0.020, "serve_tick": 0.035})


def test_a_trace_without_the_window_or_a_device_is_refused():
    raw = _raw()
    raw["spans"] = raw["spans"][1:]
    with pytest.raises(ValueError, match="bench_window"):
        tr.Trace(raw)
    raw = _raw()
    raw["devices"] = {}
    with pytest.raises(ValueError, match="no TPU"):
        tr.Trace(raw)


def test_a_recorded_decode_tick():
    """One tick of Qwen2-7B at 16 layers and 32 lanes, cut from a trace
    recorded on a TPU v5e; the ``bench_window`` mark was added around it
    (157-274 ms of the recording, less 1 ms at each end)."""
    raw = tr.extract(os.path.join(FIXTURE, "decode_tick.trace.json.gz"))
    assert list(raw["devices"]) == ["/device:TPU:0"]
    # only the thread that holds the window mark gives host spans
    assert {n for *_, n in raw["spans"]} >= {"bench_window", "device_sync",
                                            "sample_emit", "decode_dispatch"}
    assert "CommonPjRtLoadedExecutable::Execute" not in {
        n for *_, n in raw["spans"]}
    t = tr.Trace(raw)
    assert t.window_s == pytest.approx(0.115)
    assert t.busy_s == pytest.approx(0.103652445)
    # the paged kernel: once per layer, 16 calls
    kernels = [op for op in t.devices["/device:TPU:0"] if op[3]]
    assert len(kernels) == 16 and {op[3] for op in kernels} == {PAGED}
    assert t.kernel_seconds(("kernels/paged_decode.py",)) == pytest.approx(
        0.023330593)
    b = t.breakdown()
    assert ["kernel " + PAGED, pytest.approx(0.023330593)] in b["device_ops"]
    # self times never count a nested operation twice
    assert sum(v for _, v in b["device_ops"]) <= t.busy_s + 1e-9
    idle = dict(b["idle_gaps"])
    assert idle["device_sync"] == pytest.approx(0.00775976)
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s)


def test_the_profile_covers_its_part_of_the_window():
    """On the CPU: the profile opens ``lead`` seconds into the window,
    lasts ``length``, and the trace holds its mark and the spans of the
    thread that made it."""
    import glob
    import shutil
    import time

    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    p = tr.Profile(1.2)
    assert (p.lead, p.length) == (pytest.approx(0.3), pytest.approx(0.9))
    t0 = time.perf_counter()
    p.open(t0)
    try:
        while time.perf_counter() < t0 + 1.2:
            p.poll()
            with jax.profiler.TraceAnnotation("serve_tick"):
                step(x).block_until_ready()
        p.stop()
        a, b = p.window
        assert a >= t0 + 0.3 and b - a == pytest.approx(0.9, abs=0.1)
        path, = glob.glob(os.path.join(p.dir, "plugins", "profile", "*",
                                       "*.trace.json.gz"))
        raw = tr.extract(path)
    finally:
        p.stop()
        shutil.rmtree(p.dir, ignore_errors=True)
    names = {n for *_, n in raw["spans"]}
    assert {"bench_window", "serve_tick"} <= names
    mark, = [(s, e) for s, e, n in raw["spans"] if n == "bench_window"]
    assert (mark[1] - mark[0]) / 1e9 == pytest.approx(b - a, abs=0.02)
