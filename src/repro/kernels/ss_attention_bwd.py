"""Flash-style Pallas backward kernels for the spectral-shifting GEMMs.

Mirrors the two forward streams in ``ss_attention.py`` in reverse, never
materializing a (c, n) or (n, c) intermediate:

* ``landmark_summary_bwd`` — given the saved online-softmax statistics
  ``(m, l)`` and the forward output ``BV``, reconstructs each key block's
  softmax factor ``P = exp(s - m) / l`` exactly (no second reduction pass)
  and streams

      dV_blk = P^T g,   dK_blk = (P ∘ (gV^T - D))^T Q~ * scale,
      dQ~   += (P ∘ (gV^T - D)) K_blk * scale,

  where ``D = rowsum(g ∘ BV)`` is the standard flash-backward dot-product
  correction, computed once in jnp from saved tensors (O(c·dv)).

* ``query_side_bwd`` — the softmax axis (c) is block-resident, so P is
  recomputed per query block (no stats needed) and dQ/dV stream out while
  dK~ / dM / ddelta accumulate in fp32 VMEM scratch across the grid.

Both kernels accept the same ``seg``-based segment-causal masks and dynamic
``kv_offset``/``kv_valid``/``q_offset`` bounds as their forward counterparts
(see ss_attention.py): under context parallelism the backward runs per shard
against the *global* softmax statistics, so reconstruction stays exact. Grid
= (batch, n_blocks), n innermost so scratch accumulators persist across the
stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ss_attention import (
    _b_side_mask,
    _bounds_array,
    _query_side_probs,
)

_NEG_INF = -1e30


# --------------------------------------------------------------------------
# B-side backward: dQ~, dK, dV of BV = softmax(Q~ K^T * scale) @ V.
# --------------------------------------------------------------------------
def _landmark_summary_bwd_kernel(
    *refs,
    scale: float,
    n_valid: int,
    block_n: int,
    seg: int,
    dyn: bool,
):
    """Ref layout: [bounds (1,2) SMEM if dyn], q (1,c,d), k (1,bn,d),
    v (1,bn,dv), g (1,c,dv), m (1,c,1), l (1,c,1), dcoef (1,c,1),
    dq (1,c,d), dk (1,bn,d), dv (1,bn,dv), dq_scr (c,d)."""
    if dyn:
        bounds_ref, *refs = refs
        kv_offset = bounds_ref[0, 0]
        # Clamp by the local pre-block-padding length — see the forward
        # kernel: the zero tail padded to a block multiple can sit below
        # the global valid end on non-final shards.
        kv_valid = jnp.minimum(bounds_ref[0, 1], kv_offset + n_valid)
    else:
        kv_offset = 0
        kv_valid = n_valid if n_valid % block_n else None
    (q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, dcoef_ref,
     dq_ref, dk_ref, dv_ref, dq_scr) = refs
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)                      # (c, d)
    k = k_ref[0].astype(jnp.float32)                      # (bn, d)
    v = v_ref[0].astype(jnp.float32)                      # (bn, dv)
    g = g_ref[0].astype(jnp.float32)                      # (c, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                             # (c, bn)
    mask = _b_side_mask(
        s.shape, i, block_n=block_n, seg=seg, kv_offset=kv_offset,
        kv_valid=kv_valid,
    )
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)

    p = jnp.exp(s - m_ref[0]) / jnp.maximum(l_ref[0], 1e-30)  # (c, bn)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)

    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                     # (c, bn)
    ds = p * (dp - dcoef_ref[0]) * scale                  # (c, bn)

    dv_ref[0] = jax.lax.dot_general(
        p, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dv_ref.dtype)                                # (bn, dv)
    dk_ref[0] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dk_ref.dtype)                                # (bn, d)
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                     # (c, d)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def landmark_summary_bwd(
    q_l: jnp.ndarray,    # (b, c, d)
    k: jnp.ndarray,      # (b, n, d)
    v: jnp.ndarray,      # (b, n, dv)
    bv: jnp.ndarray,     # (b, c, dv)  saved forward output
    m: jnp.ndarray,      # (b, c, 1)   saved row max
    l: jnp.ndarray,      # (b, c, 1)   saved row denominator
    g: jnp.ndarray,      # (b, c, dv)  cotangent of BV
    *,
    scale: float,
    block_n: int = 512,
    causal: bool = False,
    interpret: bool = False,
    kv_offset=None,
    kv_valid=None,
    seq_len_k: int = 0,
):
    """Backward of ``landmark_summary``: returns ``(dq_l, dk, dv)``.

    Under context parallelism, pass the shard's ``kv_offset``/``kv_valid``
    plus the *global* statistics (bv, m, l) — the per-shard reconstruction
    is then exact and ``dq_l`` is the local partial to psum.
    """
    b, c, d = q_l.shape
    n, dv = k.shape[1], v.shape[2]
    n_k = seq_len_k or n
    seg = -(-n_k // c) if causal else 0
    block_n = min(block_n, n)
    n_pad = -n % block_n
    if n_pad:
        k = jnp.pad(k, ((0, 0), (0, n_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, n_pad), (0, 0)))
    n_blocks = (n + n_pad) // block_n

    # D_i = sum_j P_ij (g_i . V_j) = g_i . BV_i — O(c dv), stays in jnp.
    dcoef = jnp.sum(
        g.astype(jnp.float32) * bv.astype(jnp.float32), axis=-1, keepdims=True
    )

    dyn = kv_offset is not None or kv_valid is not None
    kernel = functools.partial(
        _landmark_summary_bwd_kernel, scale=scale, n_valid=n,
        block_n=block_n, seg=seg, dyn=dyn,
    )
    stat_spec = pl.BlockSpec((1, c, 1), lambda bi, i: (bi, 0, 0))
    in_specs = [
        pl.BlockSpec((1, c, d), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, c, dv), lambda bi, i: (bi, 0, 0)),
        stat_spec,
        stat_spec,
        stat_spec,
    ]
    inputs = [q_l, k, v, g, m, l, dcoef]
    if dyn:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        off = kv_offset if kv_offset is not None else 0
        # Defaults mirror the forward: all local keys valid, globally.
        inputs.insert(
            0,
            _bounds_array(off, kv_valid if kv_valid is not None else off + n),
        )
    dq, dk, dv_out = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, c, d), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, c, d), q_l.dtype),
            jax.ShapeDtypeStruct((b, n + n_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b, n + n_pad, dv), v.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((c, d), jnp.float32)],
        interpret=interpret,
        name="landmark_summary_bwd",
    )(*inputs)
    if n_pad:
        dk, dv_out = dk[:, :n], dv_out[:, :n]
    return dq, dk, dv_out


# --------------------------------------------------------------------------
# F-side backward: dQ, dK~, dM, dV, ddelta of
#   out = softmax(Q K~^T * scale) @ M + delta * V.
# --------------------------------------------------------------------------
def _query_side_bwd_kernel(
    *refs,
    scale: float,
    block_n: int,
    seg: int,
    pos_offset: int,
    dyn: bool,
):
    """Ref layout: [bounds (1,1) SMEM if dyn], q (1,bn,d), kl (1,c,d),
    m (1,c,dv), v (1,bn,dv), delta (1,1,1), g (1,bn,dv), dq (1,bn,d),
    dv (1,bn,dv), dkl (1,c,d), dm (1,c,dv), dd (1,1,1), dkl_scr (c,d),
    dm_scr (c,dv), dd_scr (1,1)."""
    if dyn:
        bounds_ref, *refs = refs
        pos_offset = bounds_ref[0, 0]
    (q_ref, kl_ref, m_ref, v_ref, delta_ref, g_ref,
     dq_ref, dv_ref, dkl_ref, dm_ref, dd_ref,
     dkl_scr, dm_scr, dd_scr) = refs
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dkl_scr[...] = jnp.zeros_like(dkl_scr)
        dm_scr[...] = jnp.zeros_like(dm_scr)
        dd_scr[...] = jnp.zeros_like(dd_scr)

    p = _query_side_probs(
        q_ref, kl_ref, scale=scale, block_n=block_n, seg=seg,
        pos_offset=pos_offset,
    )                                                     # (bn, c)
    q = q_ref[0].astype(jnp.float32)                      # (bn, d)
    kl = kl_ref[0].astype(jnp.float32)                    # (c, d)
    mm = m_ref[0].astype(jnp.float32)                     # (c, dv)
    v = v_ref[0].astype(jnp.float32)                      # (bn, dv)
    g = g_ref[0].astype(jnp.float32)                      # (bn, dv)

    dp = jax.lax.dot_general(
        g, mm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                     # (bn, c)
    drow = jnp.sum(p * dp, axis=-1, keepdims=True)        # (bn, 1)
    ds = p * (dp - drow) * scale                          # (bn, c)

    dq_ref[0] = jax.lax.dot_general(
        ds, kl, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)                                # (bn, d)
    dv_ref[0] = (delta_ref[0, 0, 0] * g).astype(dv_ref.dtype)
    dkl_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                     # (c, d)
    dm_scr[...] += jax.lax.dot_general(
        p, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                     # (c, dv)
    dd_scr[...] += jnp.sum(g * v)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        dkl_ref[0] = dkl_scr[...].astype(dkl_ref.dtype)
        dm_ref[0] = dm_scr[...].astype(dm_ref.dtype)
        dd_ref[0] = dd_scr[...].astype(dd_ref.dtype)


def query_side_bwd(
    q: jnp.ndarray,      # (b, n, d)
    k_l: jnp.ndarray,    # (b, c, d)
    m_mat: jnp.ndarray,  # (b, c, dv)
    v: jnp.ndarray,      # (b, n, dv)
    delta: jnp.ndarray,  # (b, 1, 1) fp32
    g: jnp.ndarray,      # (b, n, dv)  cotangent of out
    *,
    scale: float,
    block_n: int = 512,
    causal: bool = False,
    seq_len_k: int = 0,
    interpret: bool = False,
    q_offset=None,
):
    """Backward of ``query_side``: returns ``(dq, dk_l, dm, dv, ddelta)``.

    Under context parallelism ``dk_l``/``dm``/``ddelta`` are the local
    partials to psum (dq/dv stay shard-local)."""
    b, n, d = q.shape
    c, dv = k_l.shape[1], v.shape[2]
    n_k = seq_len_k or n
    seg = -(-n_k // c) if causal else 0
    pos_offset = n_k - n if causal else 0
    block_n = min(block_n, n)
    n_pad = -n % block_n
    if n_pad:
        # Padded rows contribute nothing: their cotangent is zero, which
        # zeroes ds / dq / the scratch accumulators for those rows.
        q = jnp.pad(q, ((0, 0), (0, n_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, n_pad), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, n_pad), (0, 0)))
    n_blocks = (n + n_pad) // block_n

    dyn = q_offset is not None
    kernel = functools.partial(
        _query_side_bwd_kernel, scale=scale, block_n=block_n, seg=seg,
        pos_offset=pos_offset, dyn=dyn,
    )
    in_specs = [
        pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, c, d), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, c, dv), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, 1, 1), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
    ]
    inputs = [q, k_l, m_mat, v, delta.astype(jnp.float32), g]
    if dyn:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.insert(0, _bounds_array(q_offset))
    dq, dv_out, dkl, dm, dd = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, c, d), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, c, dv), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, i: (bi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, n + n_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, n + n_pad, dv), v.dtype),
            jax.ShapeDtypeStruct((b, c, d), k_l.dtype),
            jax.ShapeDtypeStruct((b, c, dv), m_mat.dtype),
            jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((c, d), jnp.float32),
            pltpu.VMEM((c, dv), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="query_side_bwd",
    )(*inputs)
    if n_pad:
        dq, dv_out = dq[:, :n], dv_out[:, :n]
    return dq, dkl, dm, dv_out, dd
