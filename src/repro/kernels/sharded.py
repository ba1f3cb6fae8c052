"""Context-parallel (sequence-sharded) driver for the fused spectral-shift
attention: shard_map around the single-device Pallas kernels.

Why this is cheap for *this* method: the only cross-shard state is landmark-
sized. A flash kernel would need a ring exchange of full K/V blocks, but the
spectral-shift factorization reduces every cross-device interaction to
(c, d)-shaped summaries:

    landmarks   Q~/K~ — masked per-shard segment sums, one (c, d) psum;
    B-side      BV = softmax(Q~ K^T) V — each shard streams its local keys
                with the existing ``landmark_summary`` kernel and emits its
                online-softmax partials (acc, m, l); the global softmax is
                the standard flash merge: m* = pmax(m), l* = psum(l e^{m-m*}),
                BV* = psum(acc e^{m-m*}) / l* — all (c, ·)-sized collectives;
    core        U_ss/delta — O(c^3) jnp on the replicated landmarks, computed
                identically on every device (no collective);
    F-side      out = softmax(Q K~^T) M + delta V — purely shard-local: the
                softmax axis (c) is resident, queries/values are the shard's
                own rows.

Gradients flow through ``jax.custom_vjp`` ops defined *inside* the shard_map
body: the forward saves the **global** (BV, m, l) statistics (tagged
``ss_bv``/``ss_stats`` so ``remat="ss_stats"`` keeps working under SP), and
the backward runs the existing flash-backward kernels per shard against
those global stats — reconstruction is exact. Collective accounting under
``check_vma=False`` (where psum transposes to psum): the B-side backward
psums the per-shard cotangents of the replicated BV* once, and every
cotangent of a replicated *input* (dQ~, dK~, dM, ddelta) is returned as the
shard's local partial — the transpose of the psum that replicated the
primal performs the cross-shard accumulation, so an explicit reduction
would double count.

Ragged shards: n is zero-padded to a multiple of the shard count and every
kernel takes the shard's global ``kv_offset``/``q_offset`` plus the true
sequence end as dynamic bounds (SMEM scalars, see ss_attention.py), so the
padded tail never enters a softmax and sliced-off query rows carry zero
cotangent.

Entry point: ``ss_attention_fused_sharded``; model code reaches it through
``kernels.dispatch.dispatch_ss_attention``, which resolves the active mesh /
sequence axes from ``distributed.sharding.active_seq_sharding()``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.attention import SSConfig
from repro.core.landmarks import onehot_segment_sums, segment_counts
from repro.core.pinv import CORE_PRECISION
from repro.kernels.ops import _float0_like, flash_rescale, ss_core_factors
from repro.kernels.ss_attention import landmark_summary, query_side
from repro.kernels.ss_attention_bwd import landmark_summary_bwd, query_side_bwd


# --------------------------------------------------------------------------
# Sharded custom-VJP ops (used INSIDE the shard_map body).
# meta = (scale, block_n, causal, n_global, interpret, seq_axes)
# --------------------------------------------------------------------------
def _landmark_summary_sp_merge(meta, q_l, k, v, off):
    scale, block_n, causal, n_glob, interpret, axes = meta
    bv, m, l = landmark_summary(
        q_l, k, v, scale=scale, block_n=block_n, causal=causal,
        interpret=interpret, return_stats=True, kv_offset=off,
        kv_valid=n_glob, seq_len_k=n_glob,
    )
    # Flash merge of the per-shard online-softmax partials: re-anchor every
    # shard's (l, acc) to the global row max (shared ops.flash_rescale —
    # the same algebra the streaming decode state appends with), then psum.
    # ``bv`` is the locally-normalized numerator (acc / l), so acc = bv * l.
    m_g = jax.lax.pmax(m, axes)
    l_r, acc_r = flash_rescale(m, l, bv.astype(jnp.float32) * l, m_g)
    l_g = jax.lax.psum(l_r, axes)
    acc_g = jax.lax.psum(acc_r, axes)
    bv_g = (acc_g / jnp.maximum(l_g, 1e-30)).astype(v.dtype)
    return bv_g, m_g, l_g


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _landmark_summary_sp(meta, q_l, k, v, off):
    """Global BV over sequence-sharded keys. ``q_l`` replicated, ``k``/``v``
    the shard's local rows, ``off`` the shard's global key offset."""
    bv_g, _, _ = _landmark_summary_sp_merge(meta, q_l, k, v, off)
    return bv_g


def _landmark_summary_sp_fwd(meta, q_l, k, v, off):
    bv_g, m_g, l_g = _landmark_summary_sp_merge(meta, q_l, k, v, off)
    res = (
        q_l, k, v, off,
        checkpoint_name(bv_g, "ss_bv"),
        checkpoint_name(m_g, "ss_stats"),
        checkpoint_name(l_g, "ss_stats"),
    )
    return bv_g, res


def _landmark_summary_sp_bwd(meta, res, g):
    scale, block_n, causal, n_glob, interpret, axes = meta
    q_l, k, v, off, bv_g, m_g, l_g = res
    # The replicated output BV* is consumed independently by every shard's
    # downstream (each produces different out rows), so the TRUE cotangent
    # of BV* is the psum of the per-shard cotangents — reduce it once here.
    g = jax.lax.psum(g, axes)
    # Per-shard backward against the GLOBAL stats: P = exp(s - m*) / l* is
    # the exact global softmax factor restricted to local key columns, so
    # dK/dV are shard-complete and dQ~ is the shard's LOCAL partial. No
    # psum on dQ~: under ``check_vma=False`` the transpose of the psum that
    # replicated q_l is itself a psum, which accumulates the partials —
    # reducing here as well would double count.
    dq_l, dk, dv = landmark_summary_bwd(
        q_l, k, v, bv_g, m_g, l_g, g, scale=scale, block_n=block_n,
        causal=causal, interpret=interpret, kv_offset=off, kv_valid=n_glob,
        seq_len_k=n_glob,
    )
    return dq_l, dk, dv, _float0_like(off)


_landmark_summary_sp.defvjp(_landmark_summary_sp_fwd, _landmark_summary_sp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _query_side_sp(meta, q, k_l, m_mat, v, delta, off):
    """Shard-local F-side: out rows for the shard's queries at global
    offset ``off``. k_l / m_mat / delta are replicated."""
    scale, block_n, causal, n_glob, interpret, _ = meta
    return query_side(
        q, k_l, m_mat, v, delta, scale=scale, block_n=block_n, causal=causal,
        seq_len_k=n_glob, interpret=interpret, q_offset=off,
    )


def _query_side_sp_fwd(meta, q, k_l, m_mat, v, delta, off):
    return _query_side_sp(meta, q, k_l, m_mat, v, delta, off), (
        q, k_l, m_mat, v, delta, off,
    )


def _query_side_sp_bwd(meta, res, g):
    scale, block_n, causal, n_glob, interpret, axes = meta
    q, k_l, m_mat, v, delta, off = res
    # Purely shard-local op (the softmax axis c is resident): every
    # cotangent is the shard's local partial. dK~/dM/ddelta accumulate over
    # shards via the psum-transposes of the collectives that replicated
    # their primals — no explicit reduction here (see B-side note).
    dq, dkl, dm, dv, dd = query_side_bwd(
        q, k_l, m_mat, v, delta, g, scale=scale, block_n=block_n,
        causal=causal, seq_len_k=n_glob, interpret=interpret, q_offset=off,
    )
    return dq, dkl, dm, dv, dd, _float0_like(off)


_query_side_sp.defvjp(_query_side_sp_fwd, _query_side_sp_bwd)


# --------------------------------------------------------------------------
# Driver.
# --------------------------------------------------------------------------
def _shard_index(seq_axes, sizes):
    """Row-major flat shard index over (possibly multiple) mesh axes."""
    idx = jnp.int32(0)
    for ax, sz in zip(seq_axes, sizes):
        idx = idx * sz + jax.lax.axis_index(ax)
    return idx


def _masked_landmarks(x, c: int, pos, valid, seg_lm: int, n: int, axes):
    """Global segment-mean landmarks from a shard's rows: the shared
    ``onehot_segment_sums`` GEMM on GLOBAL positions, psum'd over the
    sequence axes, divided by the true global ``segment_counts`` —
    numerically the ``segment_means(via_matmul=True)`` formula."""
    oh = (
        ((pos // seg_lm)[None, :] == jnp.arange(c)[:, None])
        & valid[None, :]
    ).astype(x.dtype)                                   # (c, n_loc)
    sums = jax.lax.psum(onehot_segment_sums(x, oh), axes)  # (b, c, d)
    counts = segment_counts(n, c, seg_lm)
    return (sums / counts[:, None]).astype(x.dtype)


def ss_attention_fused_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: SSConfig = SSConfig(),
    *,
    mesh: Mesh,
    seq_axes: tuple,
    lead_axes: tuple = (),
    scale: Optional[float] = None,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sequence-sharded ``ss_attention_fused``: same math, Pallas kernels per
    shard, landmark-sized collectives. Shapes (..., n, d) with the n axis
    sharded over ``seq_axes``; leading dims flatten and shard over
    ``lead_axes`` (dropped automatically when indivisible). Differentiable
    (sharded custom-VJP ops) and segment-causal capable; self-attention only
    (n_q == n_k).
    """
    from repro.kernels.ops import ss_attention_fused

    *lead, n, d = q.shape
    n_k, dv = k.shape[-2], v.shape[-1]
    c = cfg.num_landmarks
    seq_axes = tuple(seq_axes)
    sizes = tuple(int(mesh.shape[a]) for a in seq_axes)
    n_shards = 1
    for s_ in sizes:
        n_shards *= s_
    if n != n_k:
        raise ValueError(
            "sequence-sharded fused attention is self-attention only "
            f"(n_q={n} != n_k={n_k}); route decode/cross shapes via jnp"
        )
    if n_shards <= 1 or n <= c:
        # No sharding to exploit / degenerate exact-attention regime: the
        # single-device program partitions fine under plain GSPMD.
        return ss_attention_fused(
            q, k, v, cfg, scale=scale, block_n=block_n, interpret=interpret
        )
    scale = scale if scale is not None else 1.0 / (d**0.5)
    b = 1
    for s_ in lead:
        b *= s_
    qf = q.reshape(b, n, d)
    kf = k.reshape(b, n, d)
    vf = v.reshape(b, n, dv)

    n_pad = -n % n_shards
    if n_pad:
        widths = ((0, 0), (0, n_pad), (0, 0))
        qf, kf, vf = (jnp.pad(x, widths) for x in (qf, kf, vf))
    n_loc = (n + n_pad) // n_shards
    seg_lm = -(-n // c)  # landmark segment length, from the TRUE length
    causal = cfg.causal
    meta = (scale, min(block_n, n_loc), causal, n, interpret, seq_axes)

    # Leading (batch*heads) dim keeps its sharding only when it divides.
    lead_axes = tuple(a for a in lead_axes if a in mesh.axis_names)
    lead_size = 1
    for a in lead_axes:
        lead_size *= int(mesh.shape[a])
    if lead_axes and b % lead_size:
        lead_axes = ()
    lead_spec = (lead_axes if len(lead_axes) > 1 else lead_axes[0]) if lead_axes else None
    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    spec = P(lead_spec, seq_spec, None)

    def body(q_loc, k_loc, v_loc):
        b_loc = q_loc.shape[0]
        off = _shard_index(seq_axes, sizes) * n_loc
        pos = off + jnp.arange(n_loc)
        valid = pos < n

        q_l = _masked_landmarks(q_loc, c, pos, valid, seg_lm, n, seq_axes)
        k_l = _masked_landmarks(k_loc, c, pos, valid, seg_lm, n, seq_axes)

        # Replicated c x c core — identical jnp program on every device.
        u, delta_core = ss_core_factors(q_l, k_l, cfg, scale, n)

        bv = _landmark_summary_sp(meta, q_l, k_loc, v_loc, off)  # (b, c, dv)
        m_mat = jnp.matmul(
            u.astype(jnp.float32), bv.astype(jnp.float32),
            precision=CORE_PRECISION,
        ).astype(v_loc.dtype)
        if cfg.include_shift_identity:
            delta = delta_core.astype(jnp.float32)
            v_q = v_loc
        else:
            delta = jnp.zeros((b_loc, 1, 1), jnp.float32)
            v_q = jnp.zeros_like(v_loc)
        return _query_side_sp(meta, q_loc, k_l, m_mat, v_q, delta, off)

    out = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(qf, kf, vf)
    if n_pad:
        out = out[:, :n]
    return out.reshape(*lead, n, dv)
