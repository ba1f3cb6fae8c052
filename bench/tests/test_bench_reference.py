"""A configuration names its reference module, and the loader finds it
there or stops the run with a message that names the configuration's
file; the benchmark's weights draw every norm gain as ones."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(BENCH, "tests", "fixture")
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import run  # noqa: E402


def _config(name):
    path = os.path.join(FIXTURE, "configs", name + ".json")
    with open(path) as fh:
        return json.load(fh), path


@pytest.mark.parametrize("config, runner, api", [
    ("tiny-qwen2", "serve_cell", ("Dims", "score", "served_gap")),
    ("tiny-bert", "train_cell", ("first_steps",)),
])
def test_the_loader_returns_the_module_a_configuration_names(config, runner,
                                                             api):
    cfg, path = _config(config)
    ref = run.load_reference(cfg, path, runner)
    assert ref.__name__ == cfg["reference"]
    assert ref.__file__ == os.path.join(BENCH, cfg["reference"] + ".py")
    assert ref is sys.modules[cfg["reference"]]
    assert run.REFERENCE_API[runner] == api
    assert all(callable(getattr(ref, a)) for a in api)


@pytest.mark.parametrize("reference, runner, says", [
    (None, "serve_cell", 'names no "reference"'),
    ("ref_nowhere", "serve_cell", "ref_nowhere.py"),
    ("ref_train", "serve_cell", "lacks ['Dims', 'score', 'served_gap']"),
])
def test_a_reference_that_cannot_serve_stops_the_run(reference, runner, says):
    cfg, path = _config("tiny-qwen2")
    cfg.pop("reference")
    if reference is not None:
        cfg["reference"] = reference
    with pytest.raises(SystemExit) as err:
        run.load_reference(cfg, path, runner)
    assert path in str(err.value) and says in str(err.value)


def test_find_cell_stops_on_a_configuration_without_a_reference(tmp_path):
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    cfg, _ = _config("tiny-qwen2")
    del cfg["reference"]
    (tmp_path / "configs" / "bare.json").write_text(json.dumps(cfg))
    with open(os.path.join(FIXTURE, "traffic", "tiny_open.json")) as fh:
        (tmp_path / "traffic" / "tiny_open.json").write_text(fh.read())
    bench = {"workloads": [{"name": "bare.tiny_open", "config": "bare",
                            "traffic": "tiny_open", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    with pytest.raises(SystemExit, match="bare.json"):
        run.find_cell(bench, "bare.tiny_open", base=str(tmp_path))


def _tree(cfg):
    import jax.numpy as jnp

    from repro.models.model import model_specs
    from repro.models.params import abstract_params

    return abstract_params(model_specs(cfg), jnp.dtype(cfg.param_dtype))


def _leaves(tree):
    import jax

    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree.flatten_with_path(tree)[0]}


@pytest.mark.parametrize("config", ["tiny-qwen2", "tiny-bert"])
def test_served_and_trained_trees_draw_as_before(config, monkeypatch):
    """The name rule for norm gains draws the Qwen2 and BERT trees bit for
    bit as the list of three names it replaced did."""
    import jax.numpy as jnp
    import numpy as np

    import weights
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(**_config(config)[0]["model"])
    shapes, dtype = _tree(cfg), jnp.dtype(cfg.param_dtype)
    now = _leaves(weights.make_weights(shapes, 2**33 + 5, cfg.num_layers,
                                       dtype))
    monkeypatch.setattr(weights, "is_norm_gain", lambda name: name in (
        "norm_attn", "norm_mlp", "final_norm"))
    before = _leaves(weights.make_weights(shapes, 2**33 + 5, cfg.num_layers,
                                          dtype))
    assert now.keys() == before.keys()
    for k in now:
        assert now[k].dtype == before[k].dtype
        np.testing.assert_array_equal(np.asarray(now[k]),
                                      np.asarray(before[k]), err_msg=k)
    gains = [k for k in now if "norm" in k]
    assert len(gains) == 3
    assert all(np.all(np.asarray(now[k]) == 1) for k in gains)


def test_latent_attention_norm_is_drawn_as_ones():
    import jax.numpy as jnp
    import numpy as np

    import weights
    from repro.configs.base import reduced
    from repro.configs.registry import get_config

    cfg = reduced(get_config("deepseek-v2-lite-16b"))
    drawn = _leaves(weights.make_weights(_tree(cfg), 7, cfg.num_layers,
                                         jnp.dtype(cfg.param_dtype)))
    kv = [k for k in drawn if "norm_kv" in k]
    assert kv
    for k in kv:
        assert np.all(np.asarray(drawn[k], np.float32) == 1), k
    assert np.std(np.asarray(drawn[[k for k in drawn if "w_dkv" in k][0]],
                             np.float32)) > 0.01
