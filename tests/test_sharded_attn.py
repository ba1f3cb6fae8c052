"""Context-parallel fused attention: shard_map driver equivalence tests.

Subprocess-based (4 fake host devices, same mechanism as test_multidevice):
the sharded-fused forward/backward must match both the single-device fused
kernels and the jnp-GSPMD route, including ragged final shards and
``remat="ss_stats"`` under sequence parallelism, and
``apply_seq_sharding_config`` must no longer downgrade seq-sharded cells to
the jnp backend.
"""
from __future__ import annotations

import pytest

from conftest import run_subprocess


@pytest.mark.slow
def test_sharded_fused_forward_matches_fused_and_jnp():
    """Forward parity on a 4-way sequence shard: vs the single-device fused
    kernels and vs the jnp route run under GSPMD input shardings, causal and
    bidirectional, even and ragged lengths."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.attention import SSConfig, spectral_shift_attention
from repro.kernels.ops import ss_attention_fused
from repro.kernels.sharded import ss_attention_fused_sharded

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rel = lambda a, b: float(np.max(
    np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    / np.maximum(np.abs(np.asarray(b, np.float32)), 1e-3)))
# 250: ragged last shard; (384, bn=64): 96-key shards pad 32 zero keys
# inside the kernel (regression: the pad must not leak past the global
# valid bound on non-final shards).
for n, causal, bn in [(256, False, 512), (256, True, 512), (250, True, 512),
                      (384, True, 64)]:
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, n, 32)) * 0.5
    k = jax.random.normal(ks[1], (2, n, 32)) * 0.5
    v = jax.random.normal(ks[2], (2, n, 32))
    cfg = SSConfig(num_landmarks=16, causal=causal, landmark_via_matmul=True)
    f = jax.jit(lambda q, k, v: ss_attention_fused_sharded(
        q, k, v, cfg, mesh=mesh, seq_axes=("data",), block_n=bn,
        interpret=True))
    out = f(q, k, v)
    r1 = rel(out, ss_attention_fused(q, k, v, cfg, interpret=True))
    if n % 4 == 0:
        # jnp route under GSPMD: seq-sharded inputs, same mesh (GSPMD
        # placement needs even divisibility; ragged covers the jnp ref
        # through the single-device fused comparison above).
        sh = NamedSharding(mesh, P(None, "data", None))
        ref = jax.jit(
            lambda q, k, v: spectral_shift_attention(q, k, v, cfg),
            in_shardings=(sh, sh, sh),
        )(*(jax.device_put(x, sh) for x in (q, k, v)))
    else:
        ref = spectral_shift_attention(q, k, v, cfg)
    r2 = rel(out, ref)
    assert r1 < 1e-3 and r2 < 1e-3, (n, causal, r1, r2)
print('OK')
""", num_devices=4)


@pytest.mark.slow
def test_sharded_fused_grad_matches_jnp():
    """jax.grad through the sharded custom-VJP ops == jnp-route grads."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.attention import SSConfig, spectral_shift_attention
from repro.kernels.sharded import ss_attention_fused_sharded

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rel = lambda a, b: float(np.max(
    np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
    / np.maximum(np.abs(np.asarray(b, np.float32)), 1e-3)))
for n, causal in [(256, False), (250, True)]:
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, n, 32)) * 0.5
    k = jax.random.normal(ks[1], (2, n, 32)) * 0.5
    v = jax.random.normal(ks[2], (2, n, 32))
    w = jax.random.normal(ks[3], (2, n, 32))
    cfg = SSConfig(num_landmarks=16, causal=causal, landmark_via_matmul=True)
    g_sp = jax.jit(jax.grad(lambda q, k, v: jnp.sum(ss_attention_fused_sharded(
        q, k, v, cfg, mesh=mesh, seq_axes=("data",), interpret=True) * w),
        argnums=(0, 1, 2)))(q, k, v)
    g_jnp = jax.grad(lambda q, k, v: jnp.sum(
        spectral_shift_attention(q, k, v, cfg) * w), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_sp, g_jnp):
        r = rel(a, b)
        assert r < 1e-2, (n, causal, name, r)
print('OK')
""", num_devices=4)


@pytest.mark.slow
def test_sharded_remat_ss_stats_parity():
    """remat='ss_stats' under SP: the sharded ops' tagged residuals survive
    the checkpoint policy and gradients are bit-identical to no-remat."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.attention import SSConfig
from repro.kernels.sharded import ss_attention_fused_sharded

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
ks = jax.random.split(jax.random.PRNGKey(2), 3)
q = jax.random.normal(ks[0], (2, 192, 32)) * 0.5
k = jax.random.normal(ks[1], (2, 192, 32)) * 0.5
v = jax.random.normal(ks[2], (2, 192, 32))
cfg = SSConfig(num_landmarks=16, causal=True, landmark_via_matmul=True)
def loss(q, k, v):
    return jnp.sum(ss_attention_fused_sharded(
        q, k, v, cfg, mesh=mesh, seq_axes=("data",), interpret=True) ** 2)
remat_loss = jax.checkpoint(
    loss, policy=jax.checkpoint_policies.save_only_these_names(
        "ss_bv", "ss_stats"))
g0 = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
g1 = jax.jit(jax.grad(remat_loss, argnums=(0, 1, 2)))(q, k, v)
for a, b in zip(g0, g1):
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
print('OK')
""", num_devices=4)


@pytest.mark.slow
def test_seq_sharding_config_keeps_fused_backend():
    """apply_seq_sharding_config no longer rewrites attention_backend/remat
    for seq-sharded cells (the dispatch registry routes them through the
    shard_map driver); seq_shard_fused=False restores the legacy downgrade.
    Also checks the mesh-aware dispatch key resolution."""
    run_subprocess("""
import jax
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.distributed.sharding import (
    active_seq_sharding, apply_seq_sharding_config, sharding_rules,
)
from repro.kernels import dispatch
import dataclasses

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
cfg = reduced(
    get_config("qwen2-7b"),
    attention_impl="spectral_shift_fused",
    attention_backend="auto",
    remat="ss_stats",
)
out = apply_seq_sharding_config(cfg, mesh, {"seq": "data"})
assert out.attention_backend == "auto", out.attention_backend
assert out.landmark_via_matmul
# This test process runs on the CPU backend, whose auto heuristic routes
# context-parallel cells to jnp (no tagged residuals): remat is widened
# explicitly there. A forced kernel backend keeps ss_stats untouched.
assert out.remat == "full", out.remat
forced = apply_seq_sharding_config(
    dataclasses.replace(cfg, attention_backend="interpret"), mesh,
    {"seq": "data"})
assert forced.attention_backend == "interpret"
assert forced.remat == "ss_stats", forced.remat

legacy = apply_seq_sharding_config(
    dataclasses.replace(cfg, seq_shard_fused=False), mesh, {"seq": "data"})
assert legacy.attention_backend == "jnp"
assert legacy.remat == "full"

with mesh, sharding_rules(mesh, {"seq": "data"}):
    m, seq_axes, lead_axes = active_seq_sharding()
    assert seq_axes == ("data",), seq_axes
    assert "data" not in lead_axes
key = dispatch.make_key(4096, 64, 64, "bfloat16", True, backend="tpu",
                        seq_shards=4)
assert dispatch.heuristic_plan(key).impl == "sharded"
assert dispatch.PlanKey.decode(key.encode()) == key
print('OK')
""", num_devices=4)


@pytest.mark.slow
def test_sp_trainer_matches_single_device():
    """End to end: a Trainer on a seq-sharded mesh keeps the fused backend
    and remat='ss_stats', routes through the shard_map kernels, and after 2
    steps its params match single-device training."""
    run_subprocess("""
import jax, numpy as np, tempfile
from repro.configs.base import ShapeConfig, TrainConfig, reduced
from repro.configs.registry import get_config
from repro.train.trainer import Trainer

cfg = reduced(
    get_config("qwen2-7b"),
    attention_impl="spectral_shift_fused",
    attention_backend="interpret",   # force the kernel route on CPU
    remat="ss_stats",
    num_landmarks=8,
)
shape = ShapeConfig("train_4k", 64, 4, "train")
results = []
for mesh_shape, overrides in [((1, 1), {}), ((2, 4), {"seq": "model"})]:
    devs = np.array(jax.devices()[: mesh_shape[0] * mesh_shape[1]]).reshape(
        mesh_shape)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(cfg, TrainConfig(checkpoint_dir=d, seed=0), shape, mesh,
                     rule_overrides=overrides)
        assert tr.cfg.attention_backend == "interpret", tr.cfg.attention_backend
        assert tr.cfg.remat == "ss_stats", tr.cfg.remat
        hist = tr.run(2, log_every=100)
        assert all(abs(h["loss"]) < 100 for h in hist)
        results.append([np.asarray(x, np.float32)
                        for x in jax.tree.leaves(tr.params)])
for a, b in zip(*results):
    np.testing.assert_allclose(a, b, atol=2e-4)
print('OK')
""", num_devices=8, timeout=900)
