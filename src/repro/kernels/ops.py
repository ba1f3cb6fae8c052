"""Jitted wrapper: full spectral-shifting attention backed by Pallas kernels.

``ss_attention_fused(q, k, v, ...)`` computes the same function as
``repro.core.attention.spectral_shift_attention`` — including the
segment-causal variant — with the two O(n) GEMMs executed by the Pallas
kernels in ``ss_attention.py``:

    1. landmarks            (jnp: segment means, trivial)
    2. A_s, U_ss, delta     (jnp: c x c, O(c^3) — stays on jnp autodiff)
    3. BV                   (Pallas: landmark_summary, streamed over n)
    4. M = U_ss @ BV        (jnp: c x c @ c x dv)
    5. out = F @ M + d * V  (Pallas: query_side, streamed over n)

Steps 3 and 5 carry ``jax.custom_vjp`` rules backed by the flash-style
backward kernels in ``ss_attention_bwd.py``: the forward saves the online-
softmax statistics ``(m, l)`` (B-side) instead of any (c, n)/(n, c) factor,
and the backward reconstructs the softmax streams exactly from them. The
saved residuals are tagged with ``jax.ad_checkpoint.checkpoint_name``
(names ``"ss_bv"`` / ``"ss_stats"``) so the ``remat="ss_stats"`` policy in
models/model.py keeps only these tiny tensors across the layer boundary.

``jax.grad`` therefore flows end to end: through the custom-VJP kernels for
the O(n) streams and through ordinary jnp autodiff for the cubic-small
``ss_core`` (pinv + delta) and the landmark means.

Accepts (..., n, d) with arbitrary leading dims; leading dims are flattened
into the kernel batch dim.

``kv_valid`` (optional traced scalar) enables bucketed padding: only the
first ``kv_valid`` keys enter the landmark means and the B-side softmax, so
one XLA program serves every prompt length in a bucket (serve/prefill.py).
Maskless callers must pass exact-length windows — padded zero-keys would
otherwise leak into the softmax normalization. The context-parallel
(sequence-sharded) driver lives in ``kernels/sharded.py`` and reuses the
same kernels plus the core helper below.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.core.attention import SSConfig, _softmax, full_attention
from repro.core.landmarks import masked_segment_means, segment_means
from repro.core.pinv import CORE_PRECISION
from repro.core.spectral_shift import ss_core
from repro.kernels.ss_attention import landmark_summary, query_side
from repro.kernels.ss_attention_bwd import landmark_summary_bwd, query_side_bwd


def _float0_like(x):
    """Cotangent for an integer-typed primal (or None passthrough)."""
    return None if x is None else np.zeros(jnp.shape(x), jax.dtypes.float0)


# --------------------------------------------------------------------------
# Online-softmax (flash) partial-state algebra, shared by every merge site:
# the context-parallel cross-shard combine (kernels/sharded.py) and the
# streaming decode state's per-token append (serve/decode_state.py).
#
# A partial state (m, l, acc) represents sum_j exp(s_j - m) for row max
# anchor m (l) and sum_j exp(s_j - m) * v_j (acc); the softmax output is
# acc / l. ``m`` need not be the true row max — any finite anchor gives the
# same normalized result — which is what makes the zeros-initialized empty
# state (m=0, l=0, acc=0) a valid identity element for ``flash_merge``.
# --------------------------------------------------------------------------
def flash_rescale(m, l, acc, m_new):
    """Re-anchor a partial state to ``m_new`` (>= m for stability).
    Returns the rescaled ``(l, acc)``; the new anchor is ``m_new``."""
    corr = jnp.exp(m - m_new)
    return l * corr, acc * corr


def flash_merge(m_a, l_a, acc_a, m_b, l_b, acc_b):
    """Merge two online-softmax partial states into one. Shapes broadcast;
    ``m``/``l`` carry a trailing singleton axis so the correction factors
    broadcast against ``acc`` (..., rows, dv)."""
    m = jnp.maximum(m_a, m_b)
    l_ar, acc_ar = flash_rescale(m_a, l_a, acc_a, m)
    l_br, acc_br = flash_rescale(m_b, l_b, acc_b, m)
    return m, l_ar + l_br, acc_ar + acc_br


# --------------------------------------------------------------------------
# Differentiable kernel ops. ``meta`` is a hashable tuple of static config;
# custom_vjp treats it as non-differentiable.
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def landmark_summary_op(meta, q_l, k, v, kv_valid=None):
    """Differentiable BV = softmax(Q~ K^T) @ V.  meta = (scale, block_n,
    block_c, causal, interpret). ``kv_valid`` (optional traced scalar) masks
    keys at positions >= kv_valid out of the softmax (bucketed prefill)."""
    scale, block_n, block_c, causal, interpret = meta
    return landmark_summary(
        q_l, k, v, scale=scale, block_n=block_n, block_c=block_c,
        causal=causal, interpret=interpret, kv_valid=kv_valid,
    )


def _landmark_summary_fwd(meta, q_l, k, v, kv_valid=None):
    scale, block_n, block_c, causal, interpret = meta
    bv, m, l = landmark_summary(
        q_l, k, v, scale=scale, block_n=block_n, block_c=block_c,
        causal=causal, interpret=interpret, return_stats=True,
        kv_valid=kv_valid,
    )
    res = (
        q_l, k, v,
        checkpoint_name(bv, "ss_bv"),
        checkpoint_name(m, "ss_stats"),
        checkpoint_name(l, "ss_stats"),
        kv_valid,
    )
    return bv, res


def _landmark_summary_bwd(meta, res, g):
    # block_c tiles the forward stream only; the backward kernel reconstructs
    # the softmax from the (m, l) stats with its own (full-c) block geometry.
    scale, block_n, _block_c, causal, interpret = meta
    q_l, k, v, bv, m, l, kv_valid = res
    dq, dk, dv = landmark_summary_bwd(
        q_l, k, v, bv, m, l, g, scale=scale, block_n=block_n, causal=causal,
        interpret=interpret, kv_valid=kv_valid,
    )
    return dq, dk, dv, _float0_like(kv_valid)


landmark_summary_op.defvjp(_landmark_summary_fwd, _landmark_summary_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def query_side_op(meta, q, k_l, m_mat, v, delta):
    """Differentiable out = softmax(Q K~^T) @ M + delta * V.  meta = (scale,
    block_n, causal, seq_len_k, interpret); ``delta`` must be fp32."""
    scale, block_n, causal, seq_len_k, interpret = meta
    return query_side(
        q, k_l, m_mat, v, delta, scale=scale, block_n=block_n, causal=causal,
        seq_len_k=seq_len_k, interpret=interpret,
    )


def _query_side_fwd(meta, q, k_l, m_mat, v, delta):
    out = query_side_op(meta, q, k_l, m_mat, v, delta)
    return out, (q, k_l, m_mat, v, delta)


def _query_side_bwd(meta, res, g):
    scale, block_n, causal, seq_len_k, interpret = meta
    q, k_l, m_mat, v, delta = res
    return query_side_bwd(
        q, k_l, m_mat, v, delta, g, scale=scale, block_n=block_n,
        causal=causal, seq_len_k=seq_len_k, interpret=interpret,
    )


query_side_op.defvjp(_query_side_fwd, _query_side_bwd)


# --------------------------------------------------------------------------
# The c x c spectral-shift core (jnp autodiff, replicated under sharding).
# --------------------------------------------------------------------------
def ss_core_factors(q_l, k_l, cfg: SSConfig, scale: float, n_k):
    """(U, delta) of the c x c core, exactly as the jnp reference computes
    them: fp32 softmax of the landmark score matrix, Newton–Schulz pinv +
    shift, the ``delta_scale="corrected"`` rescale, the ``eq10_literal``
    variant, and the causal lower-triangular projection.

    O(c^3)-small and batch-replicated, so the shard_map context-parallel
    driver (kernels/sharded.py) runs it unchanged per device on the
    psum-combined landmarks. ``n_k`` is the TRUE key length (may be traced
    under bucketed padding) — only the "corrected" rescale reads it.
    Returns fp32 ``u`` (..., c, c) and fp32 ``delta`` (..., 1, 1)."""
    c_count = q_l.shape[-2]
    a_mask = (
        jnp.arange(c_count)[:, None] >= jnp.arange(c_count)[None, :]
        if cfg.causal
        else None
    )
    a = _softmax(
        jnp.einsum(
            "...cd,...ed->...ce",
            q_l.astype(jnp.float32),
            k_l.astype(jnp.float32),
            precision=CORE_PRECISION,
        )
        * scale,
        a_mask,
    )
    core = ss_core(
        a,
        method=cfg.method,
        pinv_iters=cfg.pinv_iters,
        rank_tol=cfg.rank_tol,
        use_shift=cfg.use_shift,
    )
    if cfg.delta_scale == "corrected" and cfg.use_shift:
        # Beyond-paper shift rescale — mirror spectral_shift_attention.
        core = core._replace(
            delta=core.delta * (c_count / n_k),
            u=jnp.matmul(
                core.z,
                jnp.eye(c_count, dtype=core.z.dtype)
                - (core.delta * (c_count / n_k)) * core.z,
                precision=CORE_PRECISION,
            ),
        )
    if cfg.variant == "eq10_literal":
        u = jnp.matmul(
            core.z, jnp.eye(c_count, dtype=a.dtype) - core.delta * a,
            precision=CORE_PRECISION,
        )
    else:
        u = core.u
    if cfg.causal:
        # Exact pinv of the lower-triangular core is lower-triangular;
        # project the finite Newton–Schulz estimate back (no future leak).
        tril = jnp.tril(jnp.ones((c_count, c_count), bool))
        u = jnp.where(tril, u, 0.0)
    return u, core.delta


# --------------------------------------------------------------------------
# Full fused attention.
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "scale", "block_n", "block_c", "interpret"),
)
def ss_attention_fused(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: SSConfig = SSConfig(),
    *,
    scale: Optional[float] = None,
    block_n: int = 512,
    block_c: int = 0,
    interpret: bool = False,
    kv_valid=None,
) -> jnp.ndarray:
    """Pallas-backed spectral-shifting attention. Shapes (..., n, d).

    Differentiable (custom-VJP kernels) and segment-causal capable —
    ``cfg.causal=True`` applies the same masks as the jnp reference path:
    the B-/F-side masks stream inside the kernels, the (c, c) core mask and
    the lower-triangular projection of U stay in jnp.

    ``kv_valid`` (optional traced scalar): treat only the first ``kv_valid``
    positions as real — landmark means and the B-side softmax mask out the
    padded tail, so a bucket-padded prompt computes exactly what the
    unpadded call would (outputs at positions >= kv_valid are garbage the
    caller discards). Bidirectional self-attention only.

    ``block_c`` (0 = all landmarks resident) tiles the B-side kernel's
    landmark rows across an extra grid axis — an autotune degree of freedom
    for large c * dv VMEM footprints (kernels/dispatch.py sweeps it).
    """
    *lead, n, d = q.shape
    n_k = k.shape[-2]
    dv = v.shape[-1]
    c = cfg.num_landmarks
    if kv_valid is not None:
        if cfg.causal:
            raise ValueError(
                "kv_valid masking supports the bidirectional (prefill) "
                "variant only; causal bucketing needs dynamic segment masks"
            )
        if n != n_k:
            raise ValueError("kv_valid masking requires self-attention (n == n_k)")
        if n <= c:
            # Assert-guard for the exact-attention degenerate path: it has
            # no key-validity mask, so padded windows would leak — callers
            # (serve/engine.py) must slice tiny prompts to exact length.
            raise ValueError(
                f"kv_valid masking needs padded n ({n}) > num_landmarks "
                f"({c}); run degenerate prompts unpadded instead"
            )
    if n <= c and n_k <= c:
        # Degenerate small-n regime: exact attention, as the jnp path does.
        return full_attention(q, k, v, causal=cfg.causal, scale=scale)
    scale = scale if scale is not None else 1.0 / (d**0.5)
    b = 1
    for s_ in lead:
        b *= s_
    qf = q.reshape(b, n, d)
    kf = k.reshape(b, n_k, d)
    vf = v.reshape(b, n_k, dv)

    if kv_valid is not None:
        kv_valid = jnp.asarray(kv_valid, jnp.int32)
        # Dynamic-length landmark means: identical to segment_means on the
        # sliced prompt, but shape-static across the bucket.
        q_l = masked_segment_means(qf, c, kv_valid)
        k_l = masked_segment_means(kf, c, kv_valid)
    else:
        q_l = segment_means(qf, c, via_matmul=cfg.landmark_via_matmul)  # (b, c, d)
        k_l = segment_means(kf, c, via_matmul=cfg.landmark_via_matmul)
    if q_l.shape[-2] != k_l.shape[-2]:
        # Mirror the jnp path's guard: n_q <= c < n_k degenerates Q~ to
        # per-token landmarks and the (c, c) core goes rectangular.
        raise ValueError(
            "spectral-shift attention needs matching landmark counts for Q~ "
            f"and K~, got {q_l.shape[-2]} vs {k_l.shape[-2]}. For decode "
            "(n_q=1) use the jnp path with cached q_landmarks/k_landmarks."
        )

    # c x c core in jnp (fp32 softmax), causally masked like _ss_factors.
    # Under bucketed padding the key length the delta_scale="corrected"
    # rescale sees must be the TRUE prompt length, not the padded shape.
    u, delta_core = ss_core_factors(
        q_l, k_l, cfg, scale, n_k if kv_valid is None else kv_valid
    )

    bv = landmark_summary_op(
        (scale, block_n, block_c, cfg.causal, interpret), q_l, kf, vf,
        kv_valid,
    )  # (b, c, dv)
    m_mat = jnp.matmul(
        u.astype(jnp.float32), bv.astype(jnp.float32), precision=CORE_PRECISION
    ).astype(v.dtype)
    if cfg.include_shift_identity and n <= n_k:
        # + delta_ss I_n -> + delta_ss * V on the query-aligned rows of V
        # (decode convention: queries are the last n positions of the
        # n_k-long context; self-attention is the n == n_k case).
        delta = delta_core.astype(jnp.float32)
        v_q = vf if n == n_k else vf[:, n_k - n :]
    else:
        delta = jnp.zeros((b, 1, 1), jnp.float32)
        v_q = vf if n == n_k else jnp.zeros((b, n, dv), vf.dtype)
    out = query_side_op(
        (scale, block_n, cfg.causal, n_k, interpret),
        qf, k_l, m_mat, v_q, delta,
    )
    return out.reshape(*lead, n, dv)


@functools.partial(
    jax.jit, static_argnames=("cfg", "scale", "block_n", "interpret")
)
def nystrom_attention_fused(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: SSConfig = SSConfig(use_shift=False, include_shift_identity=False),
    *,
    scale: Optional[float] = None,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas-backed Nystromformer baseline (delta = 0)."""
    import dataclasses

    cfg = dataclasses.replace(cfg, use_shift=False, include_shift_identity=False)
    return ss_attention_fused(
        q, k, v, cfg, scale=scale, block_n=block_n, interpret=interpret
    )
