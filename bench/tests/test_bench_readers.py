"""Every per-layer reader on a small hand-made context: the numbers it
reads, and None where it finds nothing to read."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH, "tests", "fixture")
sys.path.insert(0, BENCH)

import counts  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000
CHIP = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
PAGED = "repro/kernels/paged_decode.py:285"
SS = "repro/kernels/ss_attention.py:"


def _config(name):
    with open(os.path.join(FIXTURE, "configs", name + ".json")) as fh:
        return json.load(fh)


def _trace(ops, spans):
    return trace_reduce.Trace({"devices": {"/device:TPU:0": ops},
                               "spans": [[0, 1000 * MS, "bench_window"]]
                               + spans})


def _serve_ctx():
    # window 0-1 s; two requests of 40 and 80 prompt tokens
    ops = [[0, 100 * MS, "fusion"],
           [100 * MS, 150 * MS, "custom-call.1", PAGED],
           [200 * MS, 300 * MS, "custom-call.2", SS + "278"],
           [300 * MS, 350 * MS, "custom-call.3", SS + "416"]]
    spans = [[180 * MS, 400 * MS, "prefill"]]
    return {
        "trace": _trace(ops, spans), "chip": CHIP, "config": _config("tiny-qwen2"),
        "window": (0.0, 1.0),
        "host_spans": [(0.0, 0.5, "serve_tick"), (0.5, 1.0, "serve_tick"),
                       (0.4, 0.5, "sample_emit"), (0.6, 0.7, "prefill")],
        "prompts": {1: [5] * 40, 2: [5] * 80},
        "due": {1: 0.0, 2: 0.5},
        "admitted": {1: 0.1, 2: 0.55},
        "times": {1: [0.2, 0.3, 0.9], 2: [0.7, 0.8]},
    }


def _read(name, ctx):
    return run.load_reader(name)(ctx)


def test_serve_readers():
    ctx = _serve_ctx()
    m = ctx["config"]["model"]
    assert _read("decode_tick_ms", ctx) == pytest.approx(500.0)
    assert _read("sample_emit_share.decode", ctx) == pytest.approx(10.0)
    assert _read("device_idle_share.decode", ctx) == pytest.approx(70.0)
    # decode steps: request 1 at positions 40, 41; request 2 at 80
    steps = [40, 41, 80]
    f = sum(counts.paged_row_stats(p, 4, 2, 32)[0] for p in steps) * 2
    b = sum(counts.paged_row_stats(p, 4, 2, 32)[1] for p in steps) * 2
    assert _read("paged_row_stats_roofline.decode", ctx) == pytest.approx(
        100 * max(f / 1e12, b / 1e9) / 0.05)
    flops = sum(counts.decode_token_flops(m, p, 256) for p in steps)
    assert _read("decode_mfu", ctx) == pytest.approx(100 * flops / 1e12)
    assert _read("queue_wait_p90_ms", ctx) == pytest.approx(95.0)
    # prefills ending in the window: both (first tokens 0.2 and 0.7)
    assert _read("prefill_ms_per_ktok", ctx) == pytest.approx(
        1e6 * 0.150 / 120)
    # the prefill at 0.6-0.7 s runs while request 1 decodes (0.2-0.9 s)
    assert _read("prefill_stall_share.online", ctx) == pytest.approx(10.0)
    assert 0 < _read("ss_kernels_roofline.prefill", ctx) < 100
    assert 0 < _read("prefill_mfu", ctx) < 100


def test_serve_readers_find_nothing_outside_the_window():
    ctx = _serve_ctx()
    ctx["window"] = (5.0, 6.0)
    for name in ("decode_tick_ms", "sample_emit_share.decode",
                 "paged_row_stats_roofline.decode", "queue_wait_p90_ms",
                 "prefill_ms_per_ktok", "ss_kernels_roofline.prefill"):
        assert _read(name, ctx) is None, name


def test_train_readers():
    config = _config("tiny-bert")
    ops = [[0, 600 * MS, "fusion"],
           [600 * MS, 700 * MS, "custom-call.5",
            "repro/kernels/ss_attention_bwd.py:180"],
           [700 * MS, 800 * MS, "custom-call.6", SS + "416"]]
    ctx = {"trace": _trace(ops, []), "chip": CHIP, "config": config,
           "window": (0.0, 1.0), "host_spans": [],
           "step_bounds": [-0.1, 0.15, 0.4, 0.65, 0.9, 1.15],
           "tokens_per_step": 512, "chips": 1}
    assert _read("device_idle_share.train", ctx) == pytest.approx(20.0)
    assert _read("host_step_gap_ms.train", ctx) == pytest.approx(50.0)
    flops = 4 * 512 * counts.train_token_flops(config["model"], 128)
    assert _read("train_mfu", ctx) == pytest.approx(100 * flops / 1e12)
    assert 0 < _read("ss_kernels_roofline.train", ctx) < 100
    # steps cut by the window's edges count by their share inside it
    ctx["step_bounds"] = [0.5, 1.5]
    assert _read("host_step_gap_ms.train", ctx) == pytest.approx(400.0)
    ctx["step_bounds"] = [0.0]
    assert _read("host_step_gap_ms.train", ctx) is None
    assert _read("ss_kernels_roofline.train", ctx) is None
