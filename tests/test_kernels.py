"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles.

Kernels execute in interpret mode (CPU); TPU is the compile target. The
sweep covers padded tails (n % block != 0), non-square head dims, and both
fp32/bf16 in/out dtypes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import SSConfig, spectral_shift_attention
from repro.core.landmarks import segment_means
from repro.kernels.ops import nystrom_attention_fused, ss_attention_fused
from repro.kernels.ref import ref_landmark_summary, ref_query_side
from repro.kernels.ss_attention import landmark_summary, query_side


def _inputs(b, n, d, dv, c, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    q = (jax.random.normal(ks[0], (b, n, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, n, d)) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (b, n, dv)).astype(dtype)
    q_l = segment_means(q, c)
    k_l = segment_means(k, c)
    return q, k, v, q_l, k_l


_TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
        jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


class TestLandmarkSummaryKernel:
    @pytest.mark.parametrize("n", [128, 384, 500])     # 500: padded tail
    @pytest.mark.parametrize("c", [16, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_oracle(self, n, c, dtype):
        q, k, v, q_l, k_l = _inputs(2, n, 32, 32, c, dtype)
        scale = 1 / 32**0.5
        out = landmark_summary(q_l, k, v, scale=scale, block_n=128, interpret=True)
        ref = ref_landmark_summary(q_l, k, v, scale)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **_TOL[dtype],
        )

    @pytest.mark.parametrize("d,dv", [(32, 64), (64, 32), (128, 128)])
    def test_rect_head_dims(self, d, dv):
        q, k, v, q_l, _ = _inputs(1, 256, d, dv, 32, jnp.float32)
        scale = 1 / d**0.5
        out = landmark_summary(q_l, k, v, scale=scale, block_n=64, interpret=True)
        ref = ref_landmark_summary(q_l, k, v, scale)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_single_block(self):
        # n < block_n: one grid step, still correct.
        q, k, v, q_l, _ = _inputs(2, 100, 32, 32, 16, jnp.float32)
        out = landmark_summary(q_l, k, v, scale=0.17, block_n=512, interpret=True)
        ref = ref_landmark_summary(q_l, k, v, 0.17)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestQuerySideKernel:
    @pytest.mark.parametrize("n", [128, 384, 500])
    @pytest.mark.parametrize("c", [16, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_oracle(self, n, c, dtype):
        q, k, v, q_l, k_l = _inputs(2, n, 32, 32, c, dtype, seed=1)
        m_mat = jax.random.normal(jax.random.PRNGKey(7), (2, c, 32)).astype(dtype)
        delta = jnp.full((2, 1, 1), 0.3, jnp.float32)
        scale = 1 / 32**0.5
        out = query_side(q, k_l, m_mat, v, delta, scale=scale, block_n=128,
                         interpret=True)
        ref = ref_query_side(q, k_l, m_mat, v, delta, scale)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **_TOL[dtype],
        )

    def test_zero_delta(self):
        q, k, v, q_l, k_l = _inputs(1, 256, 32, 32, 32, jnp.float32)
        m_mat = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 32))
        delta = jnp.zeros((1, 1, 1))
        out = query_side(q, k_l, m_mat, v, delta, scale=0.2, interpret=True)
        ref = ref_query_side(q, k_l, m_mat, v, delta, 0.2)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestFusedOp:
    @pytest.mark.parametrize("n,c", [(256, 32), (512, 64), (384, 48)])
    def test_fused_matches_jnp_path(self, n, c):
        q, k, v, *_ = _inputs(2, n, 32, 32, c, jnp.float32, seed=2)
        cfg = SSConfig(num_landmarks=c, method="iterative", pinv_iters=6)
        fused = ss_attention_fused(q, k, v, cfg, interpret=True)
        ref = spectral_shift_attention(q, k, v, cfg)
        np.testing.assert_allclose(fused, ref, atol=1e-4, rtol=1e-4)

    def test_fused_multihead_lead_dims(self):
        # (B, H, n, d) leading dims flatten into the kernel batch.
        key = jax.random.PRNGKey(5)
        q = jax.random.normal(key, (2, 4, 256, 16)) * 0.5
        k = jax.random.normal(key, (2, 4, 256, 16)) * 0.5
        v = jax.random.normal(key, (2, 4, 256, 16))
        cfg = SSConfig(num_landmarks=32)
        fused = ss_attention_fused(q, k, v, cfg, interpret=True)
        ref = spectral_shift_attention(q, k, v, cfg)
        np.testing.assert_allclose(fused, ref, atol=1e-4, rtol=1e-4)

    def test_nystrom_fused(self):
        q, k, v, *_ = _inputs(2, 256, 32, 32, 32, jnp.float32)
        fused = nystrom_attention_fused(q, k, v, interpret=True)
        from repro.core.attention import nystrom_attention

        ref = nystrom_attention(q, k, v, num_landmarks=64)
        np.testing.assert_allclose(fused, ref, atol=1e-4, rtol=1e-4)

    def test_bf16_end_to_end(self):
        q, k, v, *_ = _inputs(1, 512, 64, 64, 64, jnp.bfloat16, seed=4)
        cfg = SSConfig(num_landmarks=64)
        out = ss_attention_fused(q, k, v, cfg, interpret=True)
        assert out.dtype == jnp.bfloat16
        assert not bool(jnp.any(jnp.isnan(out.astype(jnp.float32))))


class TestCausalKernels:
    """Segment-causal masks evaluated inside the streams vs masked oracles."""

    @staticmethod
    def _ls_ref(q_l, k, v, scale):
        c, n = q_l.shape[1], k.shape[1]
        seg = -(-n // c)
        mask = jnp.arange(n)[None, :] < (jnp.arange(c)[:, None] + 1) * seg
        s = jnp.einsum("bcd,bnd->bcn", q_l, k) * scale
        s = jnp.where(mask, s, -1e30)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = jnp.where(mask, p, 0.0)
        p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
        return jnp.einsum("bcn,bnd->bcd", p, v)

    @staticmethod
    def _qs_ref(q, k_l, m_mat, v, delta, scale):
        n, c = q.shape[1], k_l.shape[1]
        seg = -(-n // c)
        mask = jnp.arange(c)[None, :] <= (jnp.arange(n)[:, None] // seg)
        s = jnp.einsum("bnd,bcd->bnc", q, k_l) * scale
        s = jnp.where(mask, s, -1e30)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = jnp.where(mask, p, 0.0)
        p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
        return jnp.einsum("bnc,bcd->bnd", p, m_mat) + delta * v

    @pytest.mark.parametrize("n", [256, 500])  # 500: padded tail
    def test_landmark_summary_causal(self, n):
        q, k, v, q_l, _ = _inputs(2, n, 32, 32, 16, jnp.float32, seed=6)
        scale = 1 / 32**0.5
        out = landmark_summary(
            q_l, k, v, scale=scale, block_n=128, causal=True, interpret=True
        )
        np.testing.assert_allclose(
            out, self._ls_ref(q_l, k, v, scale), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("n", [256, 500])
    def test_query_side_causal(self, n):
        q, k, v, q_l, k_l = _inputs(2, n, 32, 32, 16, jnp.float32, seed=7)
        m_mat = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 32))
        delta = jnp.full((2, 1, 1), 0.25, jnp.float32)
        scale = 1 / 32**0.5
        out = query_side(
            q, k_l, m_mat, v, delta, scale=scale, block_n=128, causal=True,
            interpret=True,
        )
        np.testing.assert_allclose(
            out, self._qs_ref(q, k_l, m_mat, v, delta, scale),
            atol=2e-5, rtol=2e-5,
        )

    @pytest.mark.parametrize("n,c", [(256, 32), (384, 48)])
    def test_fused_causal_matches_jnp_path(self, n, c):
        q, k, v, *_ = _inputs(2, n, 32, 32, c, jnp.float32, seed=8)
        cfg = SSConfig(num_landmarks=c, causal=True)
        fused = ss_attention_fused(q, k, v, cfg, interpret=True)
        ref = spectral_shift_attention(q, k, v, cfg)
        np.testing.assert_allclose(fused, ref, atol=1e-4, rtol=1e-4)

    def test_stats_reconstruct_softmax(self):
        """(m, l) stats reconstruct the streamed softmax factor exactly."""
        q, k, v, q_l, _ = _inputs(1, 320, 32, 32, 16, jnp.float32, seed=9)
        scale = 1 / 32**0.5
        bv, m, l = landmark_summary(
            q_l, k, v, scale=scale, block_n=128, interpret=True,
            return_stats=True,
        )
        plain = landmark_summary(
            q_l, k, v, scale=scale, block_n=128, interpret=True
        )
        np.testing.assert_allclose(bv, plain, atol=0, rtol=0)
        s = jnp.einsum("bcd,bnd->bcn", q_l, k) * scale
        p = jnp.exp(s - m) / l  # reconstructed from the saved stats
        np.testing.assert_allclose(
            jnp.sum(p, -1), jnp.ones_like(l[..., 0]), atol=1e-5, rtol=1e-5
        )
        np.testing.assert_allclose(
            jnp.einsum("bcn,bnd->bcd", p, v), bv, atol=2e-5, rtol=2e-5
        )


class TestDynamicBounds:
    """Traced kv_offset/kv_valid/q_offset bounds: the SMEM-scalar plumbing
    the shard_map driver and bucketed prefill share."""

    def test_kv_valid_masks_padded_keys(self):
        """Padded-key softmax with a traced kv_valid == unpadded kernel."""
        q, k, v, q_l, _ = _inputs(2, 192, 32, 32, 16, jnp.float32, seed=10)
        n_valid = 160
        scale = 1 / 32**0.5
        out = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, interpret=True,
            kv_valid=jnp.int32(n_valid),
        )
        ref = ref_landmark_summary(q_l, k[:, :n_valid], v[:, :n_valid], scale)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_two_shard_merge_equals_full_stream(self):
        """Manual two-shard flash merge (the shard_map combine) == one full
        stream: per-shard kernels with kv_offset plus (m, l)-weighted psum."""
        q, k, v, q_l, _ = _inputs(2, 256, 32, 32, 16, jnp.float32, seed=11)
        scale = 1 / 32**0.5
        full = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, causal=True, interpret=True
        )
        half = 128
        parts = []
        for off in (0, half):
            parts.append(landmark_summary(
                q_l, k[:, off : off + half], v[:, off : off + half],
                scale=scale, block_n=64, causal=True, interpret=True,
                return_stats=True, kv_offset=jnp.int32(off),
                kv_valid=jnp.int32(256), seq_len_k=256,
            ))
        m_g = jnp.maximum(parts[0][1], parts[1][1])
        corrs = [l * jnp.exp(m - m_g) for _, m, l in parts]
        l_g = corrs[0] + corrs[1]
        bv_g = (parts[0][0] * corrs[0] + parts[1][0] * corrs[1]) / jnp.maximum(
            l_g, 1e-30
        )
        np.testing.assert_allclose(bv_g, full, atol=2e-5, rtol=2e-5)

    def test_shard_merge_with_internal_block_padding(self):
        """Regression: a shard whose length is not a block_n multiple pads
        zero keys inside the kernel; their GLOBAL positions sit below the
        global valid end on non-final shards, so the kernel must clamp the
        bound by the local length or the pad leaks into the softmax."""
        q, k, v, q_l, _ = _inputs(2, 192, 32, 32, 16, jnp.float32, seed=20)
        scale = 1 / 32**0.5
        full = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, interpret=True
        )
        half = 96  # 96 % 64 != 0 -> 32 zero-padded keys per shard
        parts = [
            landmark_summary(
                q_l, k[:, off : off + half], v[:, off : off + half],
                scale=scale, block_n=64, interpret=True, return_stats=True,
                kv_offset=jnp.int32(off), kv_valid=jnp.int32(192),
                seq_len_k=192,
            )
            for off in (0, half)
        ]
        m_g = jnp.maximum(parts[0][1], parts[1][1])
        corrs = [l * jnp.exp(m - m_g) for _, m, l in parts]
        l_g = corrs[0] + corrs[1]
        bv_g = (parts[0][0] * corrs[0] + parts[1][0] * corrs[1]) / jnp.maximum(
            l_g, 1e-30
        )
        np.testing.assert_allclose(bv_g, full, atol=2e-5, rtol=2e-5)

    def test_kv_offset_alone_keeps_all_local_keys(self):
        """Regression: kv_offset without kv_valid must default the bound to
        offset + n (all local keys valid in global coordinates), not the
        local length n."""
        q, k, v, q_l, _ = _inputs(1, 128, 32, 32, 16, jnp.float32, seed=21)
        scale = 1 / 32**0.5
        plain = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, interpret=True
        )
        offset = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, interpret=True,
            kv_offset=jnp.int32(128),  # bidir: offset alone changes nothing
        )
        np.testing.assert_allclose(offset, plain, atol=2e-5, rtol=2e-5)

    def test_query_side_dynamic_offset(self):
        """A traced q_offset reproduces the static decode-convention mask."""
        q, k, v, q_l, k_l = _inputs(2, 128, 32, 32, 16, jnp.float32, seed=12)
        m_mat = jax.random.normal(jax.random.PRNGKey(13), (2, 16, 32))
        delta = jnp.full((2, 1, 1), 0.2, jnp.float32)
        scale = 1 / 32**0.5
        n_k = 256  # queries are the last 128 rows of a 256-token context
        static = query_side(
            q, k_l, m_mat, v, delta, scale=scale, block_n=64, causal=True,
            seq_len_k=n_k, interpret=True,
        )
        dyn = query_side(
            q, k_l, m_mat, v, delta, scale=scale, block_n=64, causal=True,
            seq_len_k=n_k, interpret=True, q_offset=jnp.int32(n_k - 128),
        )
        np.testing.assert_allclose(dyn, static, atol=0, rtol=0)

    def test_bwd_kernels_accept_bounds(self):
        """Backward kernels under dynamic bounds == slicing by hand."""
        from repro.kernels.ss_attention_bwd import landmark_summary_bwd

        q, k, v, q_l, _ = _inputs(1, 160, 32, 32, 16, jnp.float32, seed=14)
        scale = 1 / 32**0.5
        n_valid = 130
        bv, m, l = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, interpret=True,
            return_stats=True, kv_valid=jnp.int32(n_valid),
        )
        g = jax.random.normal(jax.random.PRNGKey(15), bv.shape)
        dq, dk, dv = landmark_summary_bwd(
            q_l, k, v, bv, m, l, g, scale=scale, block_n=64, interpret=True,
            kv_valid=jnp.int32(n_valid),
        )
        dq_r, dk_r, dv_r = landmark_summary_bwd(
            q_l, k[:, :n_valid], v[:, :n_valid], bv, m, l, g, scale=scale,
            block_n=64, interpret=True,
        )
        np.testing.assert_allclose(dq, dq_r, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(dk[:, :n_valid], dk_r, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(dv[:, :n_valid], dv_r, atol=2e-5, rtol=2e-5)
        assert float(jnp.max(jnp.abs(dk[:, n_valid:]))) == 0.0
        assert float(jnp.max(jnp.abs(dv[:, n_valid:]))) == 0.0


class TestMaskedFusedAttention:
    """ss_attention_fused(kv_valid=...): the bucketed-prefill contract."""

    def test_padded_equals_unpadded(self):
        q, k, v, *_ = _inputs(2, 96, 32, 32, 16, jnp.float32, seed=16)
        cfg = SSConfig(num_landmarks=16)
        for n_valid in (50, 77, 96):
            ref = ss_attention_fused(
                q[:, :n_valid], k[:, :n_valid], v[:, :n_valid], cfg,
                interpret=True,
            )
            out = ss_attention_fused(
                q, k, v, cfg, interpret=True, kv_valid=jnp.int32(n_valid)
            )
            np.testing.assert_allclose(
                out[:, :n_valid], ref, atol=1e-5, rtol=1e-5
            )

    def test_padded_equals_unpadded_corrected_delta(self):
        """Regression: the delta_scale="corrected" rescale (delta * c/n)
        must read the TRUE prompt length, not the padded shape."""
        q, k, v, *_ = _inputs(2, 96, 32, 32, 16, jnp.float32, seed=22)
        cfg = SSConfig(num_landmarks=16, delta_scale="corrected")
        n_valid = 50
        ref = ss_attention_fused(
            q[:, :n_valid], k[:, :n_valid], v[:, :n_valid], cfg,
            interpret=True,
        )
        out = ss_attention_fused(
            q, k, v, cfg, interpret=True, kv_valid=jnp.int32(n_valid)
        )
        np.testing.assert_allclose(out[:, :n_valid], ref, atol=1e-5, rtol=1e-5)

    def test_masked_landmarks_match_segment_means(self):
        from repro.core.landmarks import masked_segment_means

        x = jax.random.normal(jax.random.PRNGKey(17), (2, 80, 8))
        for n_valid in (33, 64, 80):
            got = masked_segment_means(x, 16, jnp.int32(n_valid))
            want = segment_means(x[:, :n_valid], 16, via_matmul=True)
            np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)

    def test_guards(self):
        q, k, v, *_ = _inputs(1, 64, 16, 16, 16, jnp.float32)
        with pytest.raises(ValueError, match="num_landmarks"):
            # Padded degenerate prompt: exact path has no mask (assert-guard).
            ss_attention_fused(
                q, k, v, SSConfig(num_landmarks=64), interpret=True,
                kv_valid=jnp.int32(10),
            )
        with pytest.raises(ValueError, match="bidirectional"):
            ss_attention_fused(
                q, k, v, SSConfig(num_landmarks=8, causal=True),
                interpret=True, kv_valid=jnp.int32(40),
            )


class TestBlockCTiling:
    """block_c grid tiling of the B-side kernel (autotune candidate): each
    landmark-row tile re-runs the key stream with its own scratch — results
    must be bit-comparable to the untiled kernel."""

    @pytest.mark.parametrize("block_c", [4, 8])
    @pytest.mark.parametrize("causal", [False, True])
    def test_tiled_matches_untiled(self, block_c, causal):
        q, k, v, q_l, _ = _inputs(2, 200, 32, 32, 16, jnp.float32, seed=30)
        scale = 1 / 32**0.5
        ref = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, causal=causal, interpret=True
        )
        out, m, l = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, block_c=block_c,
            causal=causal, interpret=True, return_stats=True,
        )
        np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)
        _, m_ref, l_ref = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, causal=causal,
            interpret=True, return_stats=True,
        )
        np.testing.assert_allclose(m, m_ref, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(l, l_ref, atol=1e-6, rtol=1e-6)

    def test_non_divisor_block_c_ignored(self):
        q, k, v, q_l, _ = _inputs(1, 128, 32, 32, 16, jnp.float32, seed=31)
        scale = 1 / 32**0.5
        ref = landmark_summary(q_l, k, v, scale=scale, block_n=64, interpret=True)
        out = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, block_c=5, interpret=True
        )
        np.testing.assert_allclose(out, ref, atol=0, rtol=0)

    def test_tiled_with_kv_valid(self):
        q, k, v, q_l, _ = _inputs(2, 192, 32, 32, 16, jnp.float32, seed=32)
        scale = 1 / 32**0.5
        n_valid = 150
        ref = ref_landmark_summary(q_l, k[:, :n_valid], v[:, :n_valid], scale)
        out = landmark_summary(
            q_l, k, v, scale=scale, block_n=64, block_c=8, interpret=True,
            kv_valid=jnp.int32(n_valid),
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_fused_attention_block_c_parity(self):
        q, k, v, *_ = _inputs(2, 256, 32, 32, 16, jnp.float32, seed=33)
        cfg = SSConfig(num_landmarks=16)
        ref = ss_attention_fused(q, k, v, cfg, interpret=True)
        out = ss_attention_fused(q, k, v, cfg, block_c=8, interpret=True)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


class TestPagedDecodeKernel:
    """Gather-free paged row stats (kernels/paged_decode.py): the block-
    table-aware kernel must reproduce the jnp recompute over the gathered
    dense view — including permuted tables, ragged last blocks, ZERO_BLOCK
    tail slots, and the zeros-empty-row convention — and its custom_vmap
    rule must lower the lane batch to one multi-lane launch bitwise."""

    def _setup(self, lanes=3, hkv=2, r=4, d=16, dv=8, bs=8, nb_pool=12,
               n_slots=5, seed=40):
        from repro.serve.paged import ZERO_BLOCK

        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(lanes, hkv, r, d)), jnp.float32)
        k_pool = jnp.asarray(
            rng.normal(size=(hkv, nb_pool, bs, d)), jnp.float32
        ).at[:, ZERO_BLOCK].set(0.0)
        v_pool = jnp.asarray(
            rng.normal(size=(hkv, nb_pool, bs, dv)), jnp.float32
        ).at[:, ZERO_BLOCK].set(0.0)
        # Distinct permuted blocks per lane, ZERO_BLOCK backing the tail.
        blocks = rng.permutation(np.arange(1, nb_pool))
        tables = np.full((lanes, n_slots), ZERO_BLOCK, np.int32)
        tables[0, :4] = blocks[:4]
        tables[1, :3] = blocks[4:7]
        tables[2, :5] = np.concatenate([blocks[7:], blocks[:1]])
        # ragged: none of these is a block multiple
        kv_valid = jnp.asarray([27, 17, 39], jnp.int32)
        return q, k_pool, v_pool, jnp.asarray(tables), kv_valid

    def _ref(self, q, k_pool, v_pool, tables, kv_valid, lane, scale):
        from repro.serve.decode_state import recompute_stats

        tb = np.asarray(tables[lane])
        kv = jnp.concatenate([k_pool[:, b] for b in tb], axis=1)[None]
        vv = jnp.concatenate([v_pool[:, b] for b in tb], axis=1)[None]
        return recompute_stats(
            q[lane][None], kv, vv, int(kv_valid[lane]) - 1, scale
        )

    def _setup_chunked(self, kv_valid, n_slots, lanes=2, hkv=4, r=7,
                       d=128, dv=128, bs=16, seed=41):
        """Qwen2-7B's decode geometry scaled down (4 kv heads, 7 rows per
        head, 16-token pages, d 128, float32 pools), where a chunk is 16
        pages: each lane owns distinct permuted blocks for the slots its
        ``kv_valid`` reaches and ZERO_BLOCK backs the rest of its view."""
        from repro.serve.paged import ZERO_BLOCK

        rng = np.random.default_rng(seed)
        need = [-(-kv // bs) for kv in kv_valid]
        nb_pool = sum(need) + 1
        q = jnp.asarray(rng.normal(size=(lanes, hkv, r, d)), jnp.float32)
        k_pool = jnp.asarray(
            rng.normal(size=(hkv, nb_pool, bs, d)), jnp.float32
        ).at[:, ZERO_BLOCK].set(0.0)
        v_pool = jnp.asarray(
            rng.normal(size=(hkv, nb_pool, bs, dv)), jnp.float32
        ).at[:, ZERO_BLOCK].set(0.0)
        blocks = rng.permutation(np.arange(1, nb_pool))
        tables = np.full((lanes, n_slots), ZERO_BLOCK, np.int32)
        off = 0
        for lane, n in enumerate(need):
            tables[lane, :n] = blocks[off:off + n]
            off += n
        return (q, k_pool, v_pool, jnp.asarray(tables),
                jnp.asarray(kv_valid, jnp.int32))

    # kv_valid per lane, view slots; chunks are 16 pages (256 keys) in the
    # chunked cases
    _CASES = {
        "ragged_blocks": None,                     # _setup's small case
        "slots_not_divisible": ([611, 380], 40),   # chunks of 16, 16, 8
        "empty_beside_full": ([512, 0, 300], 32),
        "chunk_boundary": ([256, 512], 48),
        "all_tail_chunks": ([20, 17], 64),         # 3 of 4 chunks empty
        "deployment_scaled": ([1000, 333, 64, 1], 64),
    }

    @pytest.mark.parametrize("case", list(_CASES))
    def test_vs_dense_recompute(self, case):
        from repro.kernels.paged_decode import paged_row_stats_lanes

        spec = self._CASES[case]
        if spec is None:
            q, k_pool, v_pool, tables, kv_valid = self._setup()
            bs = 8
        else:
            kvv, n_slots = spec
            q, k_pool, v_pool, tables, kv_valid = self._setup_chunked(
                kvv, n_slots, lanes=len(kvv))
            bs = 16
        scale = 0.3
        m, l, acc = paged_row_stats_lanes(
            q, (k_pool,), v_pool, tables, kv_valid, scale=scale,
            block_size=bs, interpret=True,
        )
        for lane in range(q.shape[0]):
            if int(kv_valid[lane]) == 0:
                # the absorbing empty state
                assert np.all(np.asarray(l[lane]) == 0.0)
                assert np.all(np.asarray(acc[lane]) == 0.0)
                assert np.all(np.asarray(m[lane]) <= -1e29)
                continue
            m_r, l_r, acc_r = self._ref(q, k_pool, v_pool, tables, kv_valid,
                                        lane, scale)
            # anchor-invariant comparisons: log-mass and normalized BV
            np.testing.assert_allclose(
                np.log(np.maximum(np.asarray(l[lane]), 1e-30))
                + np.asarray(m[lane]),
                np.asarray(jnp.log(jnp.maximum(l_r[0], 1e-30)) + m_r[0]),
                atol=1e-5, rtol=1e-5,
            )
            np.testing.assert_allclose(
                np.asarray(acc[lane] / jnp.maximum(l[lane], 1e-30)),
                np.asarray(acc_r[0] / jnp.maximum(l_r[0], 1e-30)),
                atol=1e-5, rtol=1e-5,
            )

    def test_pages_per_chunk_is_a_function_of_shapes(self):
        """The chunk size follows from the pool geometry alone: Qwen2-7B's
        float32 GQA pool and the absorbed-MLA pools each get their own,
        within the per-step byte budget and the view."""
        from repro.kernels.paged_decode import _CHUNK_BYTES, pages_per_chunk

        gqa = dict(hkv=4, splits=(128,), dv=128, block_size=16, itemsize=4,
                   n_slots=128)
        mla = dict(hkv=1, splits=(512, 64), dv=512, block_size=16,
                   itemsize=4, n_slots=128)
        p_gqa, p_mla = pages_per_chunk(**gqa), pages_per_chunk(**mla)
        assert (p_gqa, p_mla) == (16, 8)
        assert pages_per_chunk(**gqa) == p_gqa      # no hidden state
        for kw, p in ((gqa, p_gqa), (mla, p_mla)):
            page = (kw["hkv"] * kw["block_size"]
                    * (sum(kw["splits"]) + kw["dv"]) * kw["itemsize"])
            assert p & (p - 1) == 0
            assert p * page <= _CHUNK_BYTES < 2 * p * page
        assert pages_per_chunk(**{**gqa, "n_slots": 5}) == 5
        assert pages_per_chunk(**{**gqa, "itemsize": 2}) == 2 * p_gqa

    def test_custom_vmap_matches_batched_launch(self):
        from repro.kernels.paged_decode import (
            paged_row_stats, paged_row_stats_lanes,
        )

        q, k_pool, v_pool, tables, kv_valid = self._setup()
        ref = paged_row_stats_lanes(
            q, (k_pool,), v_pool, tables, kv_valid, scale=0.3, block_size=8,
            interpret=True,
        )
        got = jax.jit(jax.vmap(
            lambda qq, tt, kk: paged_row_stats(
                qq, (k_pool,), v_pool, tt, kk, scale=0.3, block_size=8,
                interpret=True,
            ),
            in_axes=(0, 0, 0),
        ))(q, tables, kv_valid)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))

    def test_lane_batched_pools_rejected(self):
        from repro.kernels.paged_decode import paged_row_stats

        q, k_pool, v_pool, tables, kv_valid = self._setup()
        k_lanes = jnp.broadcast_to(k_pool[None], (q.shape[0], *k_pool.shape))
        with pytest.raises(NotImplementedError, match="broadcast"):
            jax.vmap(
                lambda qq, kp, tt, kk: paged_row_stats(
                    qq, (kp,), v_pool, tt, kk, scale=0.3, block_size=8,
                    interpret=True,
                ),
                in_axes=(0, 0, 0, 0),
            )(q, k_lanes, tables, kv_valid)

    def test_no_valid_keys_emits_absorbing_state(self):
        """kv_valid=0: (m=-inf, l=0, acc=0) — the anchor must be absorbing
        so that flash-merging a strongly negative token score re-anchors
        at that score instead of underflowing against a finite anchor."""
        from repro.kernels.ops import flash_merge
        from repro.kernels.paged_decode import paged_row_stats

        q, k_pool, v_pool, tables, _ = self._setup()
        m, l, acc = paged_row_stats(
            q[0], (k_pool,), v_pool, tables[0], 0, scale=0.3, block_size=8,
            interpret=True,
        )
        assert np.all(np.asarray(l) == 0.0) and np.all(np.asarray(acc) == 0.0)
        assert np.all(np.asarray(m) <= -1e29)
        # merge one token with a score deep in exp-underflow territory
        s = jnp.full_like(m, -200.0)
        v = jnp.ones_like(acc)
        m2, l2, acc2 = flash_merge(m, l, acc, s, jnp.ones_like(s), v)
        np.testing.assert_allclose(np.asarray(acc2 / l2), np.asarray(v))

    def test_two_pool_split_matches_single(self):
        """MLA contract: scores accumulated across (latent, rope) pools ==
        one kernel over the feature-concatenated pool."""
        from repro.kernels.paged_decode import paged_row_stats_lanes

        q, k_pool, v_pool, tables, kv_valid = self._setup()
        ref = paged_row_stats_lanes(
            q, (k_pool,), v_pool, tables, kv_valid, scale=0.3, block_size=8,
            interpret=True,
        )
        got = paged_row_stats_lanes(
            q, (k_pool[..., :10], k_pool[..., 10:]), v_pool, tables,
            kv_valid, scale=0.3, block_size=8, interpret=True,
        )
        for g, r in zip(got, ref):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=1e-5, rtol=1e-5
            )

    def test_compiled_kernel_refuses_narrow_pools(self):
        """A DMA moves whole 128-lane tiles: compiled for TPU, the kernel
        takes pools stored at ``lane_width`` and refuses a narrower one
        instead of padding (copying) the pool on every call."""
        from repro.kernels.paged_decode import (
            lane_width, paged_row_stats_lanes,
        )

        q, k_pool, v_pool, tables, kv_valid = self._setup()
        assert [lane_width(w) for w in (1, 64, 128, 129, 576)] == [
            128, 128, 128, 256, 640]
        with pytest.raises(ValueError, match="lane_width"):
            paged_row_stats_lanes(
                q, (k_pool,), v_pool, tables, kv_valid, scale=0.3,
                block_size=8, interpret=False,
            )

    def test_split_dim_mismatch_rejected(self):
        from repro.kernels.paged_decode import paged_row_stats_lanes

        q, k_pool, v_pool, tables, kv_valid = self._setup()
        with pytest.raises(ValueError, match="sum"):
            paged_row_stats_lanes(
                q, (k_pool[..., :10],), v_pool, tables, kv_valid,
                scale=0.3, block_size=8, interpret=True,
            )
