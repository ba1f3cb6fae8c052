"""Block-table-aware Pallas decode kernel: gather-free paged attention rows.

Serving keeps K/V in shared block pools (serve/paged.py); until this kernel
every decode tick *gathered* the lane's whole paged horizon into a transient
dense view — O(S*d) HBM traffic per token even when the decode math only
needed a handful of softmax rows. This module streams K/V **directly from
the pools**, block by block, guided by the lane's block table:

    paged_row_stats(q, k_pools, v_pool, table, kv_valid)
        -> (m, l, acc)  —  the online-softmax partial state of
           softmax(scale * q . K[0..kv_valid-1]) rows, where K/V are read
           through ``table`` from the pools.

That one primitive covers everything the gather used to feed:

* the ``decode_streaming="exact"`` *active-row recompute* — the single
  landmark row whose mean still drifts is recomputed over the horizon each
  tick (serve/decode_state.py); rows here are the per-kv-head group of
  active landmark means;
* the exact-attention decode path (``decode_attention_impl="full"``, and
  with it the degenerate <=c regime where spectral shifting reduces to
  exact attention) — a single query row per head, output ``acc / l``.

The caller flash-merges the *current* token's (k, v) into the returned
partials (``kernels.ops.flash_merge``): the pools hold keys ``0..pos-1``
when the kernel runs, because the paged tick commits the new token only
*after* the step (single-block scatter, ``PagedKVCache.make_paged_step``).

Block-table contract (scalar prefetch / SMEM)
---------------------------------------------
The block table and the per-lane valid-key bound ride into the kernel as
``PrefetchScalarGridSpec`` scalar-prefetch operands — small int32 arrays
placed in SMEM and available *before* the kernel body runs, so the K/V
BlockSpec index maps can dereference them:

    k block index for grid step (lane, head, slot) = table[lane, slot]

* ``table`` (lanes, n_slots) int32: pool-block ids in logical order. Slots
  past the lane's allocated range hold ``ZERO_BLOCK`` (= 0, the reserved
  all-zero block); they are *also* masked by ``kv_valid``, so the reserved
  block's contents are never load-bearing here.
* ``kv_valid`` (lanes,) int32: number of valid keys. Key j of slot i has
  global position ``i * block_size + j`` and enters the softmax iff it is
  ``< kv_valid[lane]`` — this one bound handles both the ragged last block
  and the ZERO_BLOCK tail.
* Rows with no valid key at all return the absorbing empty state
  ``(m=-inf, l=0, acc=0)``: ``flash_merge`` then re-anchors exactly at the
  first merged score, so even strongly negative token scores cannot
  underflow. Callers always merge at least the current token before using
  or storing the partials, so the -inf anchor never reaches cache leaves.

``q`` may carry the features of several key pools concatenated on the last
axis (``k_pools`` a tuple): scores are accumulated per pool without ever
concatenating pool storage — that is how absorbed-MLA decode (latent + rope
pools) runs gather-free.

vmap contract
-------------
The public ``paged_row_stats`` is single-lane and carries a
``jax.custom_batching.custom_vmap`` rule: under the serving engine's
per-lane ``vmap`` (pools broadcast with ``in_axes=None``) it lowers to ONE
multi-lane kernel launch with the lane axis as the leading grid dimension —
bypassing the generic Pallas batching rule, which would fall back to an
explicit per-lane loop for batched scalar-prefetch operands.

Kernels are validated on CPU in interpret mode; TPU is the compile target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# --------------------------------------------------------------------------
# Kernel body: online softmax over table-selected pool blocks.
# --------------------------------------------------------------------------
def _paged_row_stats_kernel(
    tbl_ref,   # (lanes, n_slots) int32 SMEM (scalar prefetch)
    kvv_ref,   # (lanes,) int32 SMEM (scalar prefetch)
    *refs,
    scale: float,
    block_size: int,
    splits: tuple[int, ...],
):
    """Ref layout after the two scalar-prefetch operands:

        q (1, 1, r, d_tot), k_pool per split (1, 1, bs, d_p),
        v (1, 1, bs, dv),
        m_out (1, 1, r, 1), l_out (1, 1, r, 1), acc_out (1, 1, r, dv),
        m_scr (r, 1), l_scr (r, 1), acc_scr (r, dv)

    Grid (lanes, kv_heads, n_slots), slots innermost so the scratch
    accumulators persist across one lane-head's stream."""
    n_pools = len(splits)
    q_ref = refs[0]
    k_refs = refs[1:1 + n_pools]
    v_ref = refs[1 + n_pools]
    mo_ref, lo_ref, acco_ref, m_scr, l_scr, acc_scr = refs[2 + n_pools:]

    lane = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (r, d_tot)
    s = None
    off = 0
    for p, dp in enumerate(splits):
        k = k_refs[p][0, 0].astype(jnp.float32)            # (bs, d_p)
        part = jax.lax.dot_general(
            q[:, off:off + dp], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (r, bs)
        s = part if s is None else s + part
        off += dp
    s = s * scale

    # Global key positions of this slot; one bound masks the ragged last
    # block and every ZERO_BLOCK tail slot alike.
    kv_pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kv_pos < kvv_ref[lane]
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                    # (r, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p_blk = jnp.exp(s - m_new)
    # A fully-masked block has m_new == s == -inf => exp(0) == 1; zero it.
    p_blk = jnp.where(mask, p_blk, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p_blk, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p_blk, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                      # (r, dv)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        # Rows with zero valid keys keep the -inf anchor (m=NEG_INF, l=0,
        # acc=0): under flash_merge that anchor is ABSORBING, so merging
        # the current token re-anchors exactly at its score. A finite
        # anchor (e.g. the zeros state) would be kept by the merge's max
        # and can underflow exp(s - 0) for strongly negative scores —
        # callers always merge at least the current token before using or
        # storing these partials, so -inf never reaches the cache leaves.
        mo_ref[0, 0] = m_scr[...]
        lo_ref[0, 0] = l_scr[...]
        acco_ref[0, 0] = acc_scr[...]


def paged_row_stats_lanes(
    q: jnp.ndarray,           # (lanes, hkv, r, d_tot)
    k_pools,                  # tuple of (hkv, num_blocks, bs, d_p)
    v_pool: jnp.ndarray,      # (hkv, num_blocks, bs, dv)
    table: jnp.ndarray,       # (lanes, n_slots) int32
    kv_valid: jnp.ndarray,    # (lanes,) int32
    *,
    scale: float,
    block_size: int,
    interpret: bool = False,
):
    """Multi-lane kernel launch: grid (lanes, hkv, n_slots). Pools are
    shared (unbatched); each (lane, head) streams only the blocks its table
    names. Returns fp32 ``(m, l, acc)`` with shapes (lanes, hkv, r, 1) x2
    and (lanes, hkv, r, dv)."""
    k_pools = tuple(k_pools)
    lanes, hkv, r, d_tot = q.shape
    splits = tuple(int(p.shape[-1]) for p in k_pools)
    if sum(splits) != d_tot:
        raise ValueError(
            f"key-pool feature dims {splits} must sum to q's last dim {d_tot}"
        )
    dv = v_pool.shape[-1]
    n_slots = table.shape[1]
    bs = block_size

    q_idx = lambda l, h, i, tbl, kvv: (l, h, 0, 0)         # noqa: E731
    kv_idx = lambda l, h, i, tbl, kvv: (h, tbl[l, i], 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, 1, r, d_tot), q_idx)]
    in_specs += [pl.BlockSpec((1, 1, bs, dp), kv_idx) for dp in splits]
    in_specs += [pl.BlockSpec((1, 1, bs, dv), kv_idx)]
    stat_spec = pl.BlockSpec((1, 1, r, 1), q_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes, hkv, n_slots),
        in_specs=in_specs,
        out_specs=(stat_spec, stat_spec, pl.BlockSpec((1, 1, r, dv), q_idx)),
        scratch_shapes=[
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_row_stats_kernel, scale=scale, block_size=bs, splits=splits,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((lanes, hkv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((lanes, hkv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((lanes, hkv, r, dv), jnp.float32),
        ),
        interpret=interpret,
        name="paged_row_stats",
    )(
        jnp.asarray(table, jnp.int32),
        jnp.asarray(kv_valid, jnp.int32),
        q, *k_pools, v_pool,
    )


# --------------------------------------------------------------------------
# Single-lane entry point with a custom vmap rule (the decode step runs
# per lane under the engine's vmap; pools broadcast with in_axes=None).
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _lane_fn(n_pools: int, scale: float, block_size: int, interpret: bool):
    @jax.custom_batching.custom_vmap
    def fn(q, *rest):
        k_pools = rest[:n_pools]
        v_pool, table, kv_valid = rest[n_pools:]
        m, l, acc = paged_row_stats_lanes(
            q[None], k_pools, v_pool, table[None], kv_valid[None],
            scale=scale, block_size=block_size, interpret=interpret,
        )
        return m[0], l[0], acc[0]

    @fn.def_vmap
    def _vmap_rule(axis_size, in_batched, q, *rest):
        qb, *rb = in_batched
        rb = list(rb)
        pools_b = rb[:n_pools] + [rb[n_pools]]
        tb, kvb = rb[n_pools + 1], rb[n_pools + 2]
        if any(pools_b):
            raise NotImplementedError(
                "paged_row_stats: K/V pools are shared storage and must be "
                "broadcast under vmap (in_axes=None), not lane-batched"
            )

        def bcast(x, batched):
            return x if batched else jnp.broadcast_to(
                x[None], (axis_size, *jnp.shape(x))
            )

        out = paged_row_stats_lanes(
            bcast(q, qb), rest[:n_pools], rest[n_pools],
            bcast(rest[n_pools + 1], tb), bcast(rest[n_pools + 2], kvb),
            scale=scale, block_size=block_size, interpret=interpret,
        )
        return out, (True, True, True)

    return fn


def paged_row_stats(
    q: jnp.ndarray,           # (hkv, r, d_tot)
    k_pools,                  # tuple of (hkv, num_blocks, bs, d_p)
    v_pool: jnp.ndarray,      # (hkv, num_blocks, bs, dv)
    table: jnp.ndarray,       # (n_slots,) int32
    kv_valid,                 # scalar int32 (may be traced)
    *,
    scale: float,
    block_size: int,
    interpret: bool = False,
):
    """Single-lane gather-free row stats (see module docstring). Returns
    fp32 ``(m, l, acc)`` of shapes (hkv, r, 1), (hkv, r, 1), (hkv, r, dv).

    vmap-ready: lane-batching ``q``/``table``/``kv_valid`` while pools ride
    in ``in_axes=None`` lowers to one multi-lane kernel launch."""
    fn = _lane_fn(len(tuple(k_pools)), float(scale), int(block_size),
                  bool(interpret))
    return fn(
        q, *tuple(k_pools), v_pool,
        jnp.asarray(table, jnp.int32),
        jnp.asarray(kv_valid, jnp.int32).reshape(()),
    )
