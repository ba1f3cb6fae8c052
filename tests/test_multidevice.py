"""Multi-device integration tests. The test process owns the single CPU
device, so these spawn subprocesses with ``--xla_force_host_platform_device_count``
(same mechanism as the dry-run) to exercise real GSPMD partitioning,
shard_map pipeline parallelism and elastic restart."""
from __future__ import annotations

import pytest

from conftest import run_subprocess


@pytest.mark.slow
def test_pipeline_parallel_matches_reference():
    run_subprocess("""
import jax, jax.numpy as jnp
from repro.distributed.pipeline import (
    make_pipeline_forward, reference_forward, stack_stages,
)

mesh = jax.make_mesh((4,), ('pipe',), axis_types=(jax.sharding.AxisType.Auto,))
key = jax.random.PRNGKey(0)
L, D, M, mb = 8, 32, 6, 4
layers = []
for i in range(L):
    k1, k2, key = jax.random.split(key, 3)
    layers.append({'w': jax.random.normal(k1, (D, D)) * 0.2,
                   'b': jax.random.normal(k2, (D,)) * 0.1})
layer_fn = lambda p, x: jnp.tanh(x @ p['w'] + p['b'])
stage_params = stack_stages(layers, 4)
x = jax.random.normal(jax.random.PRNGKey(9), (M, mb, D))
out = jax.jit(make_pipeline_forward(layer_fn, mesh, 'pipe'))(stage_params, x)
ref = reference_forward(layer_fn, layers, x.reshape(M * mb, D)).reshape(M, mb, D)
assert jnp.allclose(out, ref, atol=1e-5), float(jnp.max(jnp.abs(out - ref)))
print('OK')
""", num_devices=4)


@pytest.mark.slow
def test_elastic_restart_recovers_and_continues():
    run_subprocess("""
import jax
from repro.configs.base import ShapeConfig, TrainConfig, reduced
from repro.configs.registry import get_config
from repro.distributed.fault_tolerance import FailureInjector, HeartbeatMonitor
from repro.train.trainer import Trainer
import tempfile

cfg = reduced(get_config('qwen2-7b'))
shape = ShapeConfig('train_4k', 128, 8, 'train')
with tempfile.TemporaryDirectory() as d:
    tcfg = TrainConfig(checkpoint_dir=d, checkpoint_every=3, total_steps=20)
    mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    inj = FailureInjector({6: ['host0']})
    mon = HeartbeatMonitor([f'host{i}' for i in range(4)], timeout_s=600)
    tr = Trainer(cfg, tcfg, shape, mesh, injector=inj, monitor=mon)
    hist = tr.run(10)
    assert tr.step == 10
    assert dict(tr.mesh.shape) == {'data': 2, 'model': 2}, dict(tr.mesh.shape)
    assert all(abs(h['loss']) < 100 for h in hist)
print('OK')
""", num_devices=8)


@pytest.mark.slow
def test_tp_sharded_training_matches_single_device():
    """Same seed, same data: TP=4 training equals single-device training."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ShapeConfig, TrainConfig, reduced
from repro.configs.registry import get_config
from repro.train.trainer import Trainer
import tempfile

cfg = reduced(get_config('qwen2-7b'))
shape = ShapeConfig('train_4k', 64, 4, 'train')
results = []
for shape_mesh in [(1, 1), (2, 4)]:
    devs = np.array(jax.devices()[: shape_mesh[0] * shape_mesh[1]]).reshape(shape_mesh)
    mesh = jax.sharding.Mesh(devs, ('data', 'model'))
    with tempfile.TemporaryDirectory() as d:
        tcfg = TrainConfig(checkpoint_dir=d, seed=0)
        tr = Trainer(cfg, tcfg, shape, mesh)
        tr.run(3, log_every=1000)
        results.append([np.asarray(x, np.float32)
                        for x in jax.tree.leaves(tr.params)])
for a, b in zip(*results):
    np.testing.assert_allclose(a, b, atol=2e-4)
print('OK')
""", num_devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_allreduce_multidevice():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.optim.compression import make_compressed_grad_allreduce

mesh = jax.make_mesh((4,), ('data',), axis_types=(jax.sharding.AxisType.Auto,))
f = make_compressed_grad_allreduce(mesh, 'data')
g = {'w': jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)}
r = {'w': jnp.zeros((64,), jnp.float32)}
red, new_r = f(g, r)
# SUM all-reduce of 4 identical replicated shards == 4x the shard
# (up to int8 quantization error, which also sums over participants).
err = float(jnp.max(jnp.abs(red['w'] - 4 * g['w'])))
scale = float(jnp.max(jnp.abs(g['w']))) / 127
assert err <= scale * 4 * 0.51 + 1e-6, (err, scale)
print('OK')
""", num_devices=4)


@pytest.mark.slow
def test_production_mesh_lowering_smoke():
    """One reduced arch lowers + compiles on the full 512-chip multi-pod
    mesh inside the test (cheap: reduced layer count)."""
    run_subprocess("""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
import jax, jax.numpy as jnp
from repro.configs.base import SHAPE_PRESETS, TrainConfig, reduced
from repro.configs.registry import get_config
from repro.launch.dryrun import run_cell

res = run_cell('qwen2-7b', 'train_4k', multi_pod=True, probe=False)
assert res['flops_total'] > 0
assert res['collectives'], 'expected collectives on the production mesh'
print('OK')
""", num_devices=512, timeout=900)
