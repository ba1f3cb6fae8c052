"""The harness finds a cell's configuration, traffic mix and per-layer
metrics by name, from files alone (a fixture registered nowhere else)."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH, "tests", "fixture")
sys.path.insert(0, BENCH)

import run  # noqa: E402


def _bench():
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_cell_is_found_by_name_from_files():
    spec = run.find_cell(_bench(), "tiny-qwen2.tiny_backlog", base=FIXTURE)
    assert spec["config"]["name"] == "tiny-qwen2"
    assert spec["traffic"]["kind"] == "backlog"
    assert [m["name"] for m in spec["end_to_end"]] == [
        "output_tokens_per_s", "setup_s"]
    # no workloads key: reported wherever the metric it moves is
    assert [m["name"] for m in spec["per_layer"]] == ["fixture_tokens"]
    read = run.load_reader("fixture_tokens", base=FIXTURE)
    ctx = {"window": (0.0, 1.0), "times": {1: [0.5, 0.9, 1.5]}}
    assert read(ctx) == 2
    assert run.read_layers(spec, ctx, base=FIXTURE) == {
        "fixture_tokens": {"value": 2.0, "unit": "tokens"}}


def test_a_metric_follows_what_it_moves():
    spec = run.find_cell(_bench(), "tiny-qwen2.tiny_open", base=FIXTURE)
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s"]
    assert spec["per_layer"] == []


def test_every_registered_cell_resolves():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for cell in bench["workloads"]:
        spec = run.find_cell(bench, cell["name"])
        assert spec["traffic"]["kind"] in run.RUNNERS
        assert spec["end_to_end"] and spec["per_layer"]
        for m in spec["per_layer"]:
            assert callable(run.load_reader(m["name"]))


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "paper-bert.train_s4k", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""
