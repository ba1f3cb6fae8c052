"""Names the device trace and the host spans carry: the spectral-shift
core's ``ss_core`` scope in the op metadata of the serving and training
programs (forward and backward), the serving programs' own names, and the
split of an engine tick's device sync into ``device_wait`` and
``logits_fetch``."""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, TrainConfig, reduced
from repro.configs.registry import get_config
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.optim.adamw import adamw_init
from repro.optim.schedules import constant
from repro.serve.engine import Request, ServeEngine
from repro.train.train_step import make_train_step

SERVE = dict(max_lanes=2, max_seq=64, block_size=8, prefill_bucket=32,
             prefill_impl="ss_fused", telemetry=True)


@pytest.fixture(scope="module")
def qwen():
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-7b")), capacity_factor=100.0,
        decode_streaming="exact",
    )
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _engine(qwen, streaming="exact", **serve):
    cfg, params = qwen
    cfg = dataclasses.replace(cfg, decode_streaming=streaming)
    return ServeEngine(cfg, params, serve=ServeConfig(**{**SERVE, **serve}))


def _names(text: str) -> set[str]:
    """Every name-stack location of a lowered program's debug info."""
    return set(re.findall(r'loc\("([^"]*)"', text))


def _core(names: set[str]) -> set[str]:
    return {n for n in names
            if re.search(r"(?:^|[/(])ss_core(?:[/)]|$)", n)}


def test_serving_programs_carry_their_names(qwen):
    paged = _engine(qwen, decode_impl="paged")
    gather = _engine(qwen, "frozen", decode_impl="gather")
    chunked = _engine(qwen, chunked_prefill=True)
    assert paged._fused_step._jitted.__name__ == "decode_paged"
    assert paged._prefill._jitted.__name__ == "prefill"
    assert gather._fused_step._jitted.__name__ == "decode_gather"
    assert gather._rebase_step._jitted.__name__ == "rebase"
    assert chunked._chunk_step._jitted.__name__ == "prefill_chunk"


def test_decode_and_prefill_programs_hold_the_core_scope(qwen):
    cfg, params = qwen
    eng = _engine(qwen, decode_impl="paged")
    lanes = eng.max_lanes
    lowered = eng._fused_step._jitted.lower(
        eng.kv._storage, params, jnp.asarray(eng.sched.tables()[:, :2]),
        jnp.zeros((lanes, 1, 1), jnp.int32), jnp.zeros(lanes, jnp.int32),
        jnp.ones(lanes, bool))
    text = lowered.as_text(debug_info=True)
    assert "@jit_decode_paged" in text
    decode = _core(_names(text))
    assert any("dot_general" in n for n in decode)  # the pinv's products

    text = eng._prefill._jitted.lower(
        params, jnp.zeros((1, 32), jnp.int32), jnp.asarray(20, jnp.int32)
    ).as_text(debug_info=True)
    assert "@jit_prefill" in text
    assert _core(_names(text))


def test_train_step_holds_the_core_scope_forward_and_backward(qwen):
    """The compiled step's op metadata, which a device trace's ``tf_op``
    copies: the core's forward ops and its backward (under
    ``transpose(...)``) both hold the scope."""
    cfg, params = qwen
    cfg = dataclasses.replace(cfg, attention_impl="spectral_shift_fused")
    step = make_train_step(cfg, TrainConfig(), constant(1e-3))
    batch = {"tokens": jnp.ones((2, 64), jnp.int32)}
    text = jax.jit(step).lower(params, adamw_init(params), batch).compile(
    ).as_text()
    core = _core(set(re.findall(r'op_name="([^"]*)"', text)))
    assert any("transpose(" not in n and "dot_general" in n for n in core)
    assert any("transpose(" in n for n in core)  # the backward


def _sync_children(events):
    """For each ``device_sync`` span, the names of the spans one level
    below it that lie inside it."""
    out = []
    for ev in events:
        if ev["name"] != "device_sync":
            continue
        lo, hi = ev["t"], ev["t"] + ev["dur_s"]
        out.append([c["name"] for c in events
                    if c["depth"] == ev["depth"] + 1 and c["name"] != "gc"
                    and lo - 1e-6 <= c["t"]
                    and c["t"] + c["dur_s"] <= hi + 1e-6])
    return out


@pytest.mark.parametrize("chunked", [False, True], ids=["two_phase",
                                                        "chunked"])
def test_tick_splits_device_sync(qwen, chunked):
    cfg, _ = qwen
    eng = _engine(qwen, decode_impl="paged", chunked_prefill=chunked)
    rng = np.random.default_rng(3)
    for u in range(2):
        eng.submit(Request(u, rng.integers(3, cfg.vocab_size, 20).tolist(),
                           max_new_tokens=4))
    eng.run()
    assert len(eng.finished) == 2
    syncs = _sync_children(eng.telemetry.tracer.events)
    assert syncs and all(kids == ["device_wait", "logits_fetch"]
                         for kids in syncs)
