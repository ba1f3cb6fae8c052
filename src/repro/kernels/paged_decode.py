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
placed in SMEM and available *before* the kernel body runs. The pools stay
in HBM; each grid step copies a *chunk* of ``pages_per_chunk`` consecutive
table slots, every kv head at once, into VMEM by DMA, and starts the next
chunk's copies before it computes (double buffering):

    pool block of chunk c, page j = table[lane, c * pages + j]

The grid is (lanes, chunks); a chunk that starts at or past the lane's
``kv_valid`` copies and computes nothing, so a lane's empty view tail
costs no bandwidth and no compute. The chunk size follows from the pool
geometry alone (``pages_per_chunk``).

* ``table`` (lanes, n_slots) int32: pool-block ids in logical order. Slots
  past the lane's allocated range hold ``ZERO_BLOCK`` (= 0, the reserved
  all-zero block); they are *also* masked by ``kv_valid``, so the reserved
  block's values never enter a result (they need only be finite: a masked
  key's weight is zero). A ragged last chunk is padded with block 0.
* ``kv_valid`` (lanes,) int32: number of valid keys. Key j of slot i has
  global position ``i * block_size + j`` and enters the softmax iff it is
  ``< kv_valid[lane]`` — this one bound handles both the ragged last block
  and the ZERO_BLOCK pages of a lane's last chunk.
* Rows with no valid key at all return the absorbing empty state
  ``(m=-inf, l=0, acc=0)``: ``flash_merge`` then re-anchors exactly at the
  first merged score, so even strongly negative token scores cannot
  underflow. Callers always merge at least the current token before using
  or storing the partials, so the -inf anchor never reaches cache leaves.

``q`` may carry the features of several key pools concatenated on the last
axis (``k_pools`` a tuple): scores are accumulated per pool without ever
concatenating pool storage — that is how absorbed-MLA decode (latent + rope
pools) runs gather-free.

Compiled for TPU, every pool's feature width is a multiple of 128
(``lane_width``): a DMA moves whole 128-lane tiles. Serving stores its
pools that wide from the start (``serve/paged.py``; MLA's 64-wide rope
keys take 128, zero-filled), so the kernel never pads or copies a pool;
the caller zero-pads its few query rows to match. Interpret mode takes
any width.

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


LANES = 128   # a TPU vector register's minor axis; HBM tiles it too


def lane_width(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles: the feature width a
    pool is stored at (serve/paged.py) so that the kernel's DMAs, which
    move whole tiles, can address its pages. On TPU a narrower minor axis
    takes the same HBM, as the layout pads it to the tile."""
    return -(-width // LANES) * LANES


# --------------------------------------------------------------------------
# Chunk size: pages streamed per grid step, from the shapes alone.
# --------------------------------------------------------------------------
_CHUNK_BYTES = 1 << 20   # K+V bytes one grid step streams, all kv heads


def pages_per_chunk(hkv: int, splits: tuple[int, ...], dv: int,
                    block_size: int, itemsize: int, n_slots: int) -> int:
    """Pages (table slots) one grid step streams: the largest power of two
    whose K and V bytes across all kv heads stay within ``_CHUNK_BYTES``,
    and at most ``n_slots``. Qwen2-7B's float32 pool (4 kv heads, d 128,
    16-token pages) gets 16; the absorbed-MLA pools (1 head, 512 + 64, dv
    512) get 8."""
    page_bytes = hkv * block_size * (sum(splits) + dv) * itemsize
    p = 1
    while 2 * p * page_bytes <= _CHUNK_BYTES:
        p *= 2
    return max(1, min(p, n_slots))


# --------------------------------------------------------------------------
# Kernel body: online softmax over table-selected pool pages, a chunk of
# pages per grid step, copied from HBM with double-buffered DMA.
# --------------------------------------------------------------------------
def _paged_row_stats_kernel(
    tbl_ref,   # (lanes, n_chunks * pages) int32 SMEM (scalar prefetch)
    kvv_ref,   # (lanes,) int32 SMEM (scalar prefetch)
    *refs,
    scale: float,
    block_size: int,
    pages: int,
    splits: tuple[int, ...],
):
    """Ref layout after the two scalar-prefetch operands:

        q (1, hkv, r, d_tot); k_pool per split (hkv, num_blocks, bs, d_p)
        and v_pool (hkv, num_blocks, bs, dv), both left in HBM;
        m_out (1, hkv, r, 1), l_out (1, hkv, r, 1), acc_out (1, hkv, r, dv);
        k_buf per split (2, hkv, pages * bs, d_p), v_buf (2, hkv, pages *
        bs, dv), sems DMA (2,), m_scr (hkv, r, 1), l_scr (hkv, r, 1),
        acc_scr (hkv, r, dv), state SMEM (2,)

    Grid (lanes, n_chunks), chunks innermost and run in order, so the
    accumulators persist across one lane's stream and each step can start
    the copies of the next chunk it will compute: the lane's next chunk,
    else the next lane's first. ``state`` holds the buffer of the current
    chunk and whether its copies were started ahead. A chunk that starts
    at or past the lane's ``kv_valid`` copies and computes nothing."""
    n_pools = len(splits)
    q_ref = refs[0]
    k_hbm = refs[1:1 + n_pools]
    v_hbm = refs[1 + n_pools]
    mo_ref, lo_ref, acco_ref = refs[2 + n_pools:5 + n_pools]
    k_bufs = refs[5 + n_pools:5 + 2 * n_pools]
    v_buf, sems, m_scr, l_scr, acc_scr, state = refs[5 + 2 * n_pools:]

    lane, c = pl.program_id(0), pl.program_id(1)
    lanes, n_chunks = pl.num_programs(0), pl.num_programs(1)
    chunk = pages * block_size
    hkv = q_ref.shape[1]

    def copies(l, ci, buf):
        """One DMA per page and pool, every kv head at once."""
        out = []
        for j in range(pages):
            blk = tbl_ref[l, ci * pages + j]
            rows = pl.ds(j * block_size, block_size)
            for src, dst in zip((*k_hbm, v_hbm), (*k_bufs, v_buf)):
                out.append(pltpu.make_async_copy(
                    src.at[:, blk], dst.at[buf, :, rows], sems.at[buf]))
        return out

    @pl.when((lane == 0) & (c == 0))
    def _first_step():
        state[0] = 0
        state[1] = 0

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_valid = kvv_ref[lane]

    @pl.when(c * chunk < kv_valid)
    def _step():
        buf = state[0]

        @pl.when(state[1] == 0)
        def _start_own():
            for cp in copies(lane, c, buf):
                cp.start()

        more_here = (c + 1) * chunk < kv_valid
        nxt = jnp.minimum(lane + 1, lanes - 1)
        has_next = more_here | ((lane + 1 < lanes) & (kvv_ref[nxt] > 0))

        @pl.when(has_next)
        def _start_next():
            for cp in copies(jnp.where(more_here, lane, nxt),
                             jnp.where(more_here, c + 1, 0), 1 - buf):
                cp.start()

        state[0] = jnp.where(has_next, 1 - buf, buf)
        state[1] = has_next.astype(jnp.int32)
        for cp in copies(lane, c, buf):
            cp.wait()

        # Global key positions of this chunk; one bound masks the ragged
        # last page and every ZERO_BLOCK tail page alike.
        kv_pos = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], chunk), 1)
        mask = kv_pos < kv_valid
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)            # (r, d_tot)
            s = None
            off = 0
            for p, dp in enumerate(splits):
                k = k_bufs[p][buf, h].astype(jnp.float32)  # (chunk, d_p)
                part = jax.lax.dot_general(
                    q[:, off:off + dp], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                          # (r, chunk)
                s = part if s is None else s + part
                off += dp
            s = jnp.where(mask, s * scale, _NEG_INF)

            m_prev = m_scr[h]                              # (r, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # A row whose keys are all masked has m_new == s == NEG_INF, so
            # exp(0) == 1 there; zero it.
            p_blk = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p_blk, axis=-1,
                                                 keepdims=True)
            pv = jax.lax.dot_general(
                p_blk, v_buf[buf, h].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                              # (r, dv)
            acc_scr[h] = acc_scr[h] * corr + pv
            m_scr[h] = m_new

    @pl.when(c == n_chunks - 1)
    def _finalize():
        # Rows with zero valid keys keep the -inf anchor (m=NEG_INF, l=0,
        # acc=0): under flash_merge that anchor is ABSORBING, so merging
        # the current token re-anchors exactly at its score. A finite
        # anchor (e.g. the zeros state) would be kept by the merge's max
        # and can underflow exp(s - 0) for strongly negative scores —
        # callers always merge at least the current token before using or
        # storing these partials, so -inf never reaches the cache leaves.
        mo_ref[0] = m_scr[...]
        lo_ref[0] = l_scr[...]
        acco_ref[0] = acc_scr[...]


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
    """Multi-lane kernel launch: grid (lanes, n_chunks), each step a chunk
    of ``pages_per_chunk`` table slots for every kv head. Pools are shared
    (unbatched) and stay in HBM; each lane copies only the chunks its
    ``kv_valid`` reaches. Unless ``interpret``, every ``d_p`` and ``dv``
    is a multiple of 128 (see the module docstring). Returns fp32 ``(m,
    l, acc)`` with shapes (lanes, hkv, r, 1) x2 and (lanes, hkv, r,
    dv)."""
    k_pools = tuple(k_pools)
    lanes, hkv, r, d_tot = q.shape
    splits = tuple(int(p.shape[-1]) for p in k_pools)
    if sum(splits) != d_tot:
        raise ValueError(
            f"key-pool feature dims {splits} must sum to q's last dim {d_tot}"
        )
    dv = v_pool.shape[-1]
    # Mosaic slices an HBM ref for a DMA only in whole 128-lane tiles, so a
    # compiled kernel takes pools stored at ``lane_width``; padding one
    # here would copy the whole pool on every call.
    if not interpret and any(w != lane_width(w) for w in (*splits, dv)):
        raise ValueError(
            f"pool feature widths {splits} and {dv} must be multiples of "
            f"{LANES}: store the pools lane-padded (lane_width)"
        )
    n_slots = table.shape[1]
    bs = block_size
    pages = pages_per_chunk(hkv, splits, dv, bs, v_pool.dtype.itemsize,
                            n_slots)
    n_chunks = -(-n_slots // pages)
    # A ragged last chunk reads block 0 (ZERO_BLOCK, always allocated) in
    # its padded slots; clamping kv_valid to the view keeps them masked.
    table = jnp.pad(jnp.asarray(table, jnp.int32),
                    ((0, 0), (0, n_chunks * pages - n_slots)))
    kv_valid = jnp.minimum(jnp.asarray(kv_valid, jnp.int32), n_slots * bs)

    q_idx = lambda l, c, tbl, kvv: (l, 0, 0, 0)            # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    stat_spec = pl.BlockSpec((1, hkv, r, 1), q_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes, n_chunks),
        in_specs=[pl.BlockSpec((1, hkv, r, d_tot), q_idx)]
        + [hbm] * (len(splits) + 1),
        out_specs=(stat_spec, stat_spec,
                   pl.BlockSpec((1, hkv, r, dv), q_idx)),
        scratch_shapes=[
            *(pltpu.VMEM((2, hkv, pages * bs, dp), p.dtype)
              for p, dp in zip(k_pools, splits)),
            pltpu.VMEM((2, hkv, pages * bs, dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, 1), jnp.float32),
            pltpu.VMEM((hkv, r, dv), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _paged_row_stats_kernel, scale=scale, block_size=bs, pages=pages,
        splits=splits,
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((lanes, hkv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((lanes, hkv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((lanes, hkv, r, dv), jnp.float32),
        ),
        # Steps run in order: each one starts the copies of the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_row_stats",
    )(table, kv_valid, q, *k_pools, v_pool)
    return m, l, acc


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
