"""Tests for the performance-loop features (EXPERIMENTS.md §Perf): they must
be mathematically identical to the baselines they replace."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core.attention import SSConfig, chunked_attention, full_attention, \
    spectral_shift_attention
from repro.core.landmarks import segment_means


class TestMatmulSegmentMeans:
    @pytest.mark.parametrize("n,m", [(256, 32), (250, 32), (64, 64), (512, 8)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_identical_to_reshape(self, n, m, dtype):
        x = (jax.random.normal(jax.random.PRNGKey(0), (2, 3, n, 16))).astype(dtype)
        a = segment_means(x, m)
        b = segment_means(x, m, via_matmul=True)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-6 if dtype == jnp.float32 else 3e-2,
        )

    def test_ss_attention_same_output(self):
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32)) * 0.5
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 32))
        a = spectral_shift_attention(q, q, v, SSConfig(num_landmarks=32))
        b = spectral_shift_attention(
            q, q, v, SSConfig(num_landmarks=32, landmark_via_matmul=True)
        )
        np.testing.assert_allclose(a, b, atol=1e-5)


class TestUnrollScans:
    def test_chunked_attention_unrolled_identical(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 200, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 200, 16))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 200, 16))
        a = chunked_attention(q, k, v, causal=True, block=64)
        b = chunked_attention(q, k, v, causal=True, block=64, unroll=True)
        np.testing.assert_allclose(a, b, atol=1e-6)

    def test_mlstm_unrolled_identical(self):
        from repro.models.ssm import mlstm_chunked

        key = jax.random.PRNGKey(0)
        B, H, S, D = 1, 2, 128, 8
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D))
                   for i in range(3))
        ilog = jax.random.normal(jax.random.PRNGKey(3), (B, H, S)) * 0.1
        flog = jax.nn.log_sigmoid(
            jax.random.normal(jax.random.PRNGKey(4), (B, H, S)) + 2
        )
        a, _ = mlstm_chunked(q, k, v, ilog, flog, chunk=32)
        b, _ = mlstm_chunked(q, k, v, ilog, flog, chunk=32, unroll=True)
        np.testing.assert_allclose(a, b, atol=1e-5)

    def test_mamba_unrolled_identical(self):
        from repro.models.ssm import mamba_forward, mamba_specs
        from repro.models.params import init_params

        d, di, st = 16, 32, 8
        p = init_params(mamba_specs(d, di, st, 4, 8), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, d))
        a, _ = mamba_forward(p, x, st, chunk=32)
        b, _ = mamba_forward(p, x, st, chunk=32, unroll=True)
        np.testing.assert_allclose(a, b, atol=1e-5)


class TestWorkingParams:
    def test_noop_when_dtypes_match(self):
        from repro.configs.base import reduced
        from repro.configs.registry import get_config
        from repro.models.model import working_params

        cfg = reduced(get_config("qwen2-7b"))  # compute f32 == param f32
        tree = {"w": jnp.ones((2, 2), jnp.float32)}
        out = working_params(tree, cfg)
        assert out["w"].dtype == jnp.float32

    def test_casts_float_leaves_only(self):
        import dataclasses

        from repro.configs.base import reduced
        from repro.configs.registry import get_config
        from repro.models.model import working_params

        cfg = dataclasses.replace(
            reduced(get_config("qwen2-7b")), compute_dtype="bfloat16"
        )
        tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
        out = working_params(tree, cfg)
        assert out["w"].dtype == jnp.bfloat16
        assert out["i"].dtype == jnp.int32


@pytest.mark.slow
class TestEPMoE:
    def test_matches_gspmd_reference(self):
        run_subprocess("""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.distributed.sharding import sharding_rules
from repro.models.moe import moe_forward, moe_forward_ep, moe_specs
from repro.models.params import init_params

cfg = ModelConfig(moe=True, num_experts=8, top_k=2, moe_d_ff=32, d_model=16,
                  num_shared_experts=1, capacity_factor=100.0)
p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 12, 16)) * 0.5
mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(jax.sharding.AxisType.Auto,) * 2)
ref, aux_ref = moe_forward(p, cfg, x)
with mesh, sharding_rules(mesh):
    ep, aux_ep = jax.jit(lambda p_, x_: moe_forward_ep(p_, cfg, x_))(p, x)
assert jnp.allclose(ref, ep, atol=2e-5), float(jnp.max(jnp.abs(ref - ep)))
assert abs(float(aux_ref) - float(aux_ep)) < 1e-5
g1 = jax.grad(lambda x_: jnp.sum(moe_forward(p, cfg, x_)[0] ** 2))(x)
with mesh, sharding_rules(mesh):
    g2 = jax.jit(jax.grad(
        lambda x_: jnp.sum(moe_forward_ep(p, cfg, x_)[0] ** 2)))(x)
assert jnp.allclose(g1, g2, atol=1e-4), float(jnp.max(jnp.abs(g1 - g2)))
print('OK')
""", num_devices=8)

    def test_capacity_drops_consistent(self):
        """With tight capacity both paths drop tokens; outputs stay finite
        and within the convex range of expert outputs."""
        run_subprocess("""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.distributed.sharding import sharding_rules
from repro.models.moe import moe_forward_ep, moe_specs
from repro.models.params import init_params

cfg = ModelConfig(moe=True, num_experts=8, top_k=2, moe_d_ff=32, d_model=16,
                  capacity_factor=0.5)
p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16))
mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh, sharding_rules(mesh):
    out, aux = jax.jit(lambda p_, x_: moe_forward_ep(p_, cfg, x_))(p, x)
assert bool(jnp.all(jnp.isfinite(out)))
assert bool(jnp.isfinite(aux))
print('OK')
""", num_devices=8)


def test_ep_falls_back_without_mesh():
    from repro.configs.base import ModelConfig
    from repro.models.moe import moe_forward, moe_forward_ep, moe_specs
    from repro.models.params import init_params

    cfg = ModelConfig(moe=True, num_experts=4, top_k=2, moe_d_ff=16, d_model=8)
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8))
    a, _ = moe_forward(p, cfg, x)
    b, _ = moe_forward_ep(p, cfg, x)  # no mesh context -> fallback
    np.testing.assert_allclose(a, b, atol=1e-6)


class TestFusedModelPath:
    def test_fused_attention_impl_matches_jnp(self):
        """attention_impl='spectral_shift_fused' (Pallas kernels) == the jnp
        spectral_shift path on a bidirectional site (whisper encoder)."""
        import dataclasses

        from repro.configs.base import reduced
        from repro.configs.registry import get_config
        from repro.models.model import model_forward, model_specs
        from repro.models.params import init_params

        base = reduced(get_config("whisper-base"))
        params = init_params(model_specs(base), jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(1, base.vocab_size, (2, 16)),
                                  jnp.int32),
            "frames": jnp.asarray(rng.normal(size=(2, 64, base.d_model)),
                                  jnp.float32),
        }
        outs = {}
        for impl in ("spectral_shift", "spectral_shift_fused"):
            cfg = dataclasses.replace(base, encoder_attention_impl=impl,
                                      num_landmarks=8)
            logits, _ = model_forward(params, cfg, batch)
            outs[impl] = np.asarray(logits, np.float32)
        # Online-softmax streaming reorders the fp32 accumulation; through
        # two encoder layers + decoder the noise floor is ~5e-4 on logits.
        np.testing.assert_allclose(
            outs["spectral_shift"], outs["spectral_shift_fused"],
            atol=1e-3, rtol=1e-3,
        )
