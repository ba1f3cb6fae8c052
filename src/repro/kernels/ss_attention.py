"""Pallas TPU kernels for spectral-shifting attention (DESIGN.md §3).

Two kernels cover the only O(n) GEMMs in the method; everything else is
O(c^2)-small and stays in jnp:

* ``landmark_summary``  (B-side): ``BV = softmax(Q~ K^T) @ V``. The c landmark
  queries are VMEM-resident; K/V stream HBM->VMEM in ``block_n`` chunks with
  the online-softmax (flash) recurrence, so no (c, n) intermediate ever
  exists. Grid = (batch, n_blocks), n innermost so the fp32 accumulators in
  VMEM scratch persist across the stream. ``return_stats=True`` additionally
  emits the per-row online-softmax statistics ``(m, l)`` — the residuals the
  custom-VJP backward kernel (ss_attention_bwd.py) uses to reconstruct the
  softmax factor exactly without a second reduction pass.

* ``query_side`` (F-side): ``out = softmax(Q K~^T) @ M + delta * V`` with
  ``M = U_ss (BV)`` (c x dv, VMEM-resident). Softmax axis is c (fully
  resident) so each Q/V block needs exactly one HBM read and one write —
  the (n, c) matrix F is never materialized.

Both kernels take ``seg`` (landmark segment length, 0 = bidirectional) for
the segment-causal variant: landmark row r only attends keys in segments
<= r (B-side), and query position p only attends landmark columns
<= segment_of(p) (F-side) — the same masks ``core.attention._ss_factors``
applies on the jnp path, evaluated inside the stream.

Dynamic bounds (context parallelism + bucketed prefill): the kernels
additionally accept *traced* scalar coordinates, shipped to the kernel as a
tiny SMEM input so no per-length recompilation or (c, n) mask tensor is ever
needed:

* ``kv_offset`` / ``kv_valid`` (B-side): global position of the first local
  key and the global end of valid keys. A shard_map shard passes its shard
  offset (ragged last shards mask the tail); bucketed prefill passes
  ``kv_valid = n_valid`` so padded zero-keys never enter the softmax.
* ``q_offset`` (F-side): global position of the first local query row,
  replacing the static decode-convention ``n_k - n`` offset.

Block shapes default to MXU/VPU-aligned sizes (lane dim = head_dim, ideally
a multiple of 128; sublane blocks multiples of 8). Kernels are validated on
CPU in interpret mode against ``ref.py``; TPU is the compile target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _bounds_array(*vals) -> jnp.ndarray:
    """Pack scalar coordinates (Python ints or traced scalars) into the
    (1, len(vals)) int32 SMEM operand the dynamic-bounds kernels read."""
    return jnp.stack(
        [jnp.asarray(v, jnp.int32).reshape(()) for v in vals]
    ).reshape(1, len(vals))


def _b_side_mask(shape, i, *, block_n: int, seg: int, kv_offset=0,
                 kv_valid=None, row_offset=0):
    """Key-validity x segment-causal mask for one streamed B-side block
    (shape (c, bn) at block index ``i``), or None when nothing is masked.
    ``kv_offset``/``kv_valid`` are *global* key coordinates and may be
    Python ints (static path) or traced scalars (dynamic bounds);
    ``row_offset`` is the global landmark index of the block's first row
    (non-zero when the c axis is grid-tiled via ``block_c``). Shared by
    the forward step and the backward kernel so the two can never drift
    apart."""
    if kv_valid is None and not seg:
        return None
    # Global position of each streamed key column.
    kv_pos = kv_offset + i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1
    )
    mask = None
    if kv_valid is not None:
        # Keys past the valid end (zero-padded tail / bucketed prefill pad).
        mask = kv_pos < kv_valid
    if seg:
        # Segment-causal: landmark row r (the mean of segment r) attends
        # keys up to the end of its own segment only.
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row_offset
        cmask = kv_pos < (row + 1) * seg
        mask = cmask if mask is None else jnp.logical_and(mask, cmask)
    return mask


# --------------------------------------------------------------------------
# B-side: landmark summary with online softmax over the streamed n axis.
# --------------------------------------------------------------------------
def _landmark_summary_step(
    q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *,
    scale: float, block_n: int, seg: int, kv_offset, kv_valid,
    n_index, row_offset,
):
    """One online-softmax step over key/value block ``n_index`` (shared by
    the plain and the stats-emitting kernel). ``row_offset`` is the global
    landmark index of q_ref's first row (c-tiled grids)."""
    i = n_index

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                      # (c, d)
    k = k_ref[0].astype(jnp.float32)                      # (bn, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                             # (c, bn)

    mask = _b_side_mask(
        s.shape, i, block_n=block_n, seg=seg, kv_offset=kv_offset,
        kv_valid=kv_valid, row_offset=row_offset,
    )
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                   # (c, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                # (c, bn)
    if mask is not None:
        # exp underflows to 0 for real scores, but a fully-masked row in the
        # first block has m_new == s == -inf => exp(0) == 1; zero explicitly.
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                        # (c, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                     # (c, dv)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new


def _landmark_summary_kernel(
    *refs,
    scale: float,
    n_valid: int,
    block_n: int,
    block_c: int,
    seg: int,
    dyn: bool,
    stats: bool,
):
    """Shared kernel body. Ref layout (inputs, outputs, scratch):

        [bounds (1,2) SMEM if dyn], q (1,bc,d), k (1,bn,d), v (1,bn,dv),
        o (1,bc,dv) [, m_out (1,bc,1), l_out (1,bc,1) if stats],
        m_scr (bc,1), l_scr (bc,1), acc_scr (bc,dv)

    ``block_c`` > 0 means the landmark axis is grid-tiled: the grid is
    (b, c_tiles, n_blocks) with the streamed n axis innermost (scratch
    re-inits per tile at n block 0), otherwise (b, n_blocks).
    """
    c_tiled = block_c > 0
    n_ax = 2 if c_tiled else 1
    n_index = pl.program_id(n_ax)
    row_offset = pl.program_id(1) * block_c if c_tiled else 0
    if dyn:
        bounds_ref, *refs = refs
        kv_offset = bounds_ref[0, 0]
        # Clamp the global bound by the local pre-block-padding length:
        # keys at local index >= n_valid are the zero tail the wrapper
        # padded to a block multiple, and their global positions can sit
        # below the global valid end on non-final shards.
        kv_valid = jnp.minimum(bounds_ref[0, 1], kv_offset + n_valid)
    else:
        kv_offset = 0
        kv_valid = n_valid if n_valid % block_n else None
    if stats:
        q_ref, k_ref, v_ref, o_ref, mo_ref, lo_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs

    _landmark_summary_step(
        q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
        scale=scale, block_n=block_n, seg=seg, kv_offset=kv_offset,
        kv_valid=kv_valid, n_index=n_index, row_offset=row_offset,
    )

    @pl.when(n_index == pl.num_programs(n_ax) - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        if stats:
            mo_ref[0] = m_scr[...]
            lo_ref[0] = l_scr[...]


def landmark_summary(
    q_l: jnp.ndarray,  # (b, c, d)
    k: jnp.ndarray,    # (b, n, d)
    v: jnp.ndarray,    # (b, n, dv)
    *,
    scale: float,
    block_n: int = 512,
    block_c: int = 0,
    causal: bool = False,
    interpret: bool = False,
    return_stats: bool = False,
    kv_offset=None,
    kv_valid=None,
    seq_len_k: int = 0,
):
    """BV = softmax(Q~ K^T * scale) @ V via a flash-style streamed kernel.

    ``causal=True`` applies the segment-causal B-mask (landmark r sees keys
    < (r+1)*seg with seg = ceil(seq_len_k/c)). ``return_stats=True`` returns
    ``(bv, m, l)`` with ``m``/``l`` (b, c, 1) fp32 — the online-softmax max
    and denominator, saved as custom-VJP residuals.

    ``kv_offset``/``kv_valid`` (optional, possibly traced scalars) place the
    local keys in global coordinates: key column j has global position
    ``kv_offset + j`` and is masked unless it is < ``kv_valid``. A shard_map
    shard passes its shard offset; bucketed prefill passes the prompt length.
    ``seq_len_k`` is the *global* key length the causal segment geometry is
    built from (defaults to the local n).

    ``block_c`` (0 = disabled) tiles the landmark rows over an extra grid
    axis: rows are independent online-softmax streams, so each (1, block_c)
    tile re-runs the n stream with a block_c-row scratch — smaller VMEM
    accumulators at the price of re-reading K/V per tile. Only used when it
    divides c; an autotune candidate, not a default.
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

    c_tiled = 0 < block_c < c and c % block_c == 0
    bc = block_c if c_tiled else c
    if c_tiled:
        grid = (b, c // bc, n_blocks)
        q_idx = lambda bi, ci, i: (bi, ci, 0)      # noqa: E731
        kv_idx = lambda bi, ci, i: (bi, i, 0)      # noqa: E731
    else:
        grid = (b, n_blocks)
        q_idx = lambda bi, i: (bi, 0, 0)           # noqa: E731
        kv_idx = lambda bi, i: (bi, i, 0)          # noqa: E731

    dyn = kv_offset is not None or kv_valid is not None
    in_specs = [
        pl.BlockSpec((1, bc, d), q_idx),
        pl.BlockSpec((1, block_n, d), kv_idx),
        pl.BlockSpec((1, block_n, dv), kv_idx),
    ]
    inputs = [q_l, k, v]
    if dyn:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        off = kv_offset if kv_offset is not None else 0
        # kv_valid defaults to "all local keys valid" in GLOBAL coordinates
        # (off + n, not n): the two bounds are independently optional.
        inputs.insert(
            0,
            _bounds_array(off, kv_valid if kv_valid is not None else off + n),
        )
    scratch_shapes = [
        pltpu.VMEM((bc, 1), jnp.float32),
        pltpu.VMEM((bc, 1), jnp.float32),
        pltpu.VMEM((bc, dv), jnp.float32),
    ]
    kernel = functools.partial(
        _landmark_summary_kernel, scale=scale, n_valid=n, block_n=block_n,
        block_c=bc if c_tiled else 0, seg=seg, dyn=dyn, stats=return_stats,
    )
    if not return_stats:
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bc, dv), q_idx),
            out_shape=jax.ShapeDtypeStruct((b, c, dv), v.dtype),
            scratch_shapes=scratch_shapes,
            interpret=interpret,
            name="landmark_summary",
        )(*inputs)

    stat_spec = pl.BlockSpec((1, bc, 1), q_idx)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, bc, dv), q_idx),
            stat_spec,
            stat_spec,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, c, dv), v.dtype),
            jax.ShapeDtypeStruct((b, c, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, c, 1), jnp.float32),
        ),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="landmark_summary",
    )(*inputs)


# --------------------------------------------------------------------------
# F-side: fused softmax(Q K~^T) @ M + delta * V over streamed Q/V blocks.
# --------------------------------------------------------------------------
def _query_side_probs(q_ref, kl_ref, *, scale, block_n, seg, pos_offset):
    """Block-resident softmax factor P (bn, c), with the segment-causal
    F-mask applied when ``seg`` is set. ``pos_offset`` may be a Python int
    or a traced scalar (dynamic bounds). Shared with the backward kernel."""
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                      # (bn, d)
    kl = kl_ref[0].astype(jnp.float32)                    # (c, d)
    s = jax.lax.dot_general(
        q, kl, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                             # (bn, c)
    mask = None
    if seg:
        # Query at position p attends landmark columns <= p // seg only.
        qpos = (
            i * block_n
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            + pos_offset
        )
        lseg = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = lseg <= qpos // seg
        s = jnp.where(mask, s, _NEG_INF)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def _query_side_kernel(
    *refs,
    scale: float,
    block_n: int,
    seg: int,
    pos_offset: int,
    dyn: bool,
):
    """Ref layout: [bounds (1,1) SMEM if dyn], q (1,bn,d), kl (1,c,d),
    m (1,c,dv), v (1,bn,dv), delta (1,1,1), o (1,bn,dv)."""
    if dyn:
        bounds_ref, *refs = refs
        pos_offset = bounds_ref[0, 0]
    q_ref, kl_ref, m_ref, v_ref, delta_ref, o_ref = refs
    p = _query_side_probs(
        q_ref, kl_ref, scale=scale, block_n=block_n, seg=seg,
        pos_offset=pos_offset,
    )
    out = jax.lax.dot_general(
        p, m_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                     # (bn, dv)
    out = out + delta_ref[0, 0, 0] * v_ref[0].astype(jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


def query_side(
    q: jnp.ndarray,      # (b, n, d)
    k_l: jnp.ndarray,    # (b, c, d)
    m_mat: jnp.ndarray,  # (b, c, dv)
    v: jnp.ndarray,      # (b, n, dv)
    delta: jnp.ndarray,  # (b, 1, 1)
    *,
    scale: float,
    block_n: int = 512,
    causal: bool = False,
    seq_len_k: int = 0,
    interpret: bool = False,
    q_offset=None,
) -> jnp.ndarray:
    """out = softmax(Q K~^T * scale) @ M + delta * V, one HBM pass over Q/V.

    ``causal=True`` applies the segment-causal F-mask; ``seq_len_k`` is the
    key-sequence length the landmark segments were built from (defaults to
    n, i.e. self-attention; a longer context puts the queries at its tail,
    the decode convention). ``q_offset`` (optional, possibly traced scalar)
    *replaces* the static tail offset with the global position of q row 0 —
    the shard_map driver passes its shard offset here.
    """
    b, n, d = q.shape
    c, dv = k_l.shape[1], v.shape[2]
    n_k = seq_len_k or n
    seg = -(-n_k // c) if causal else 0
    pos_offset = n_k - n if causal else 0
    block_n = min(block_n, n)
    n_pad = -n % block_n
    if n_pad:
        q = jnp.pad(q, ((0, 0), (0, n_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, n_pad), (0, 0)))
    n_blocks = (n + n_pad) // block_n

    dyn = q_offset is not None
    in_specs = [
        pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, c, d), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, c, dv), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, 1, 1), lambda bi, i: (bi, 0, 0)),
    ]
    inputs = [q, k_l, m_mat, v, delta.astype(jnp.float32)]
    if dyn:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.insert(0, _bounds_array(q_offset))
    kernel = functools.partial(
        _query_side_kernel, scale=scale, block_n=block_n, seg=seg,
        pos_offset=pos_offset, dyn=dyn,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_n, dv), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n + n_pad, dv), q.dtype),
        interpret=interpret,
        name="query_side",
    )(*inputs)
    return out[:, :n] if n_pad else out
