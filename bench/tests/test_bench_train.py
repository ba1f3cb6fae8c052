"""A training run end to end on the CPU at a small size, past the look
for a chip: a sound run is correct; the float8 control, a step that
returns its state unchanged and half of the batch left out are not."""
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH, "tests", "fixture")
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import faults  # noqa: E402
import train_cell  # noqa: E402

NUMBERS = ("loss_gap", "first_grad_gap", "update_gap")


def _spec():
    with open(os.path.join(FIXTURE, "configs", "tiny-bert.json")) as fh:
        config = json.load(fh)
    return {"cell": {"name": "tiny", "chips": 1}, "config": config,
            "traffic": {"kind": "train"}, "end_to_end": [], "per_layer": []}


def _run(**kw):
    return train_cell.run(_spec(), seed=2**31 + 3, seconds=0.5, trace=False,
                          t_start=time.perf_counter(), **kw)


def _fails(readings, limits):
    return [k for k in NUMBERS if readings[k] > limits[k]]


def test_sound_run_is_correct_and_control_is_not():
    res = _run(control=True)
    assert res["correct"], res["checks"]
    assert res["end_to_end"]["train_tokens_per_s"] > 0
    # every leaf moves: embeddings, norms, head and the stacked layers,
    # the MLP's two biases among them
    assert res["scored"]["leaves_compared"] == 13
    limits = _spec()["config"]["limits"]
    assert _fails(res["scored"]["control"], limits)
    assert not all(c["ok"] for c in res["control_checks"].values())


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_a_planted_fault_is_caught(fault):
    res = _run(fault=faults.TRAIN[fault])
    assert not res["correct"], res["checks"]
