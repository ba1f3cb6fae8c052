"""The readers of the program's names and spans on hand-made contexts:
``logits_fetch_idle_share.decode``, ``ss_core_share.decode``,
``ss_core_share.train`` and ``program_load_s``; each reads None where the
program wrote nothing for it (as a program without the names writes)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import name_scopes  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000
CORE = "jit(decode_paged)/vmap()/while/body/closed_call/ss_core"


def _read(name, ctx):
    return run.load_reader(name)(ctx)


def _trace(ops, spans=()):
    return trace_reduce.Trace({"devices": {"/device:TPU:0": ops},
                               "spans": [[0, 1000 * MS, "bench_window"]]
                               + list(spans)})


def test_logits_fetch_idle_share():
    # busy 0-100 ms and 150-300 ms; fetches 90-160 and 400-450 ms, idle
    # inside them 100-150 and 400-450 ms: 100 ms of the 1 s window
    ops = [[0, 100 * MS, "fusion", "", "jit(decode_paged)/dot_general:"],
           [150 * MS, 300 * MS, "fusion", "", "jit(decode_paged)/add:"]]
    spans = [[90 * MS, 160 * MS, "logits_fetch"],
             [400 * MS, 450 * MS, "logits_fetch"],
             [60 * MS, 90 * MS, "device_wait"]]
    ctx = {"trace": _trace(ops, spans)}
    assert _read("logits_fetch_idle_share.decode", ctx) == pytest.approx(10.0)
    ctx = {"trace": _trace(ops, [[60 * MS, 160 * MS, "device_sync"]])}
    assert _read("logits_fetch_idle_share.decode", ctx) is None


@pytest.mark.parametrize("cell", ["decode", "train"])
def test_ss_core_share_counts_a_loop_and_its_body_once(cell):
    ops = [[0, 200 * MS, "while", "", CORE + "/while:"],
           [10 * MS, 50 * MS, "fusion", "", CORE + "/while/body/dot_general:"],
           [300 * MS, 350 * MS, "fusion", "",
            "jit(train_step)/transpose(jvp())/while/body/closed_call/"
            "ss_core/add_any:"],
           [350 * MS, 400 * MS, "fusion", "",
            "jit(f)/transpose(jvp(ss_core))/mul:"],
           # not the core: another scope and a longer name
           [500 * MS, 900 * MS, "fusion", "",
            "jit(decode_paged)/vmap()/while/body/closed_call/dot_general:"],
           [900 * MS, 950 * MS, "fusion", "", "jit(f)/ss_core_factors/mul:"]]
    ctx = {"trace": _trace(ops)}
    assert _read(f"ss_core_share.{cell}", ctx) == pytest.approx(30.0)
    ctx = {"trace": _trace(ops[4:])}
    assert _read(f"ss_core_share.{cell}", ctx) is None


def test_scope_seconds_clips_to_the_window():
    tr = _trace([[-100 * MS, 100 * MS, "fusion", "", CORE + "/mul:"],
                 [950 * MS, 1100 * MS, "fusion", "", CORE + "/add:"]])
    assert name_scopes.scope_seconds(tr, "ss_core") == pytest.approx(0.15)
    assert name_scopes.holds("ss_core")("ss_core")
    assert not name_scopes.holds("ss_core")("jit(f)/xss_core/mul:")


def test_program_load_s_sums_the_run():
    ctx = {"window": (10.0, 18.0),
           "host_spans": [(1.0, 3.5, "program_load"),
                          (4.0, 4.5, "program_load"),
                          (12.0, 12.1, "serve_tick")]}
    # set-up's loads count, though they lie before the window
    assert _read("program_load_s", ctx) == pytest.approx(3.0)
    ctx["host_spans"] = ctx["host_spans"][2:]
    assert _read("program_load_s", ctx) is None
