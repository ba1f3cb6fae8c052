"""A serving run end to end on the CPU at a small size, past the look for
a chip: the reference agrees with the program, a sound run is correct,
the float8 control and a token altered where it is produced are not, and
a configuration's reference may live in a file of its own anywhere."""
import json
import os
import sys
import textwrap
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH, "tests", "fixture")
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import faults  # noqa: E402
import run  # noqa: E402
import serve_cell  # noqa: E402

CONFIG = os.path.join(FIXTURE, "configs", "tiny-qwen2.json")


def _spec(traffic="tiny_backlog", reference=None, **model):
    with open(CONFIG) as fh:
        config = json.load(fh)
    config["model"].update(model)
    with open(os.path.join(FIXTURE, "traffic", traffic + ".json")) as fh:
        mix = json.load(fh)
    return {"cell": {"name": "tiny", "chips": 1}, "config": config,
            "traffic": mix,
            "reference": reference or run.load_reference(config, CONFIG,
                                                         "serve_cell"),
            "end_to_end": [], "per_layer": []}


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


def test_a_reference_in_a_file_of_its_own_decides_correct(tmp_path):
    """A new architecture's cell needs no edit of the harness: a
    configuration that names a reference module which lives only in
    another directory is run, and that module decides ``correct``."""
    (tmp_path / "ref_elsewhere.py").write_text(textwrap.dedent("""
        import ref_qwen2
        from ref_qwen2 import Dims, served_gap

        SCORED = []


        def score(params, prompt, served, dims, quant):
            SCORED.append((len(prompt), len(served), quant))
            return ref_qwen2.score(params, prompt, served, dims, quant)
    """))
    with open(CONFIG) as fh:
        config = dict(json.load(fh), reference="ref_elsewhere")
    where = str(tmp_path / "configs" / "tiny-elsewhere.json")
    ref = run.load_reference(config, where, "serve_cell",
                             base=str(tmp_path))
    assert ref.__file__ == str(tmp_path / "ref_elsewhere.py")
    res = _run(_spec(reference=ref), 11)
    assert res["correct"], res["checks"]
    assert ref.SCORED and {q for _, _, q in ref.SCORED} == {"f32"}
    assert sum(n for _, n, _ in ref.SCORED) == res["scored"]["tokens"] > 0
