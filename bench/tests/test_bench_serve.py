"""A serving run end to end on the CPU at a small size, past the look for
a chip: the reference agrees with the program, a sound run is correct,
the float8 control and a token altered where it is produced are not."""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH, "tests", "fixture")
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import faults  # noqa: E402
import serve_cell  # noqa: E402


def _spec(traffic="tiny_backlog", **model):
    with open(os.path.join(FIXTURE, "configs", "tiny-qwen2.json")) as fh:
        config = json.load(fh)
    config["model"].update(model)
    with open(os.path.join(FIXTURE, "traffic", traffic + ".json")) as fh:
        mix = json.load(fh)
    return {"cell": {"name": "tiny", "chips": 1}, "config": config,
            "traffic": mix, "end_to_end": [], "per_layer": []}


def _run(spec, seed, **kw):
    return serve_cell.run(spec, seed=seed, seconds=2.0, trace=False,
                          t_start=time.perf_counter(), **kw)


def test_reference_agrees_with_the_program_in_float32():
    res = _run(_spec(param_dtype="float32", compute_dtype="float32"), 3)
    assert res["scored"]["tokens"] > 50
    assert res["scored"]["gap"] < 1e-4


def test_sound_run_is_correct_and_control_is_not():
    spec = _spec("tiny_open")
    res = _run(spec, 2**31 + 77, control=True)
    limit = spec["config"]["limits"]["served_logit_gap"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["end_to_end"]) >= {"ttft_p90_ms", "itl_p95_ms",
                                      "output_tokens_per_s", "setup_s"}
    assert res["scored"]["control_gap"] > limit >= res["scored"]["gap"]


def test_a_token_altered_where_it_is_produced_is_caught():
    with faults.altered_tokens():
        res = _run(_spec(), 5)
    assert not res["correct"]
    assert not res["checks"]["served_logit_gap"]["ok"]
