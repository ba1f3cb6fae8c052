"""Per-token decode latency and HBM traffic vs cache horizon:
recompute vs streaming state x gather vs gather-free paged ticks.

The legacy spectral-shift decode rebuilds the landmark-to-key softmax
``B = softmax(Q~ K^T)`` and its value summary ``B V`` over the whole cache
horizon every tick — O(c*S*d) per token, linear in S with slope c. The
streaming decode state (serve/decode_state.py) carries per-landmark
online-softmax partials in the cache instead:

    exact   — flash-append + ONE row recomputed per tick: O(S*d + c*d),
              linear with slope 1 (a c-fold cut), token-identical greedy;
    frozen  — fully streamed O(c*d) per tick (near-flat in S) plus an
              amortized two-row rebase at segment boundaries.

Storage/tick-program cells (``impl``):

    dense   — donated jitted ``decode_step`` on a lane-dense cache (pure
              decode-math cost, no paging at all);
    gather  — block-pool storage, legacy tick: gather a transient dense
              view -> batched step -> scatter the touched block
              (``PagedKVCache.make_fused_step``). O(S) HBM bytes per tick
              in EVERY mode (this was called "paged" in pre-PR5 CSVs);
    paged   — gather-free tick (``make_paged_step`` +
              ``ServeConfig.decode_impl="paged"``): the block-table Pallas
              kernel streams K/V straight from the pools, the new token
              commits via a single-block scatter. Frozen-mode ticks touch
              O(c*d) dense state plus ONE block — per-token bytes
              independent of the horizon. (No ``recompute`` cell: that
              mode needs the dense B rebuild and stays on gather.)

Each cell reports measured ``per_token_ms`` and modelled ``per_token_bytes``
— an analytic per-tick HBM-traffic account (view assembles, horizon reads,
block commits, dense-leaf read+write) computed from the storage layout;
XLA cost analysis is useless here because scatter/dynamic-update ops are
charged at full-operand size regardless of in-place aliasing. On CPU the
paged kernel runs in interpret mode, so its measured exact-mode wall-clock
carries interpreter overhead by design (TPU is the compile target); the
frozen-mode cells and every bytes column are layout facts, not interpreter
artifacts. Caches are seeded synthetically (random K/V + consistent
landmark sums + exact streaming stats) so the 32k cell doesn't need a
32k-token prefill. Frozen per-token numbers charge the boundary rebase at
its amortized steady-state rate (one rebase per ``seg = ceil(S/c)``
tokens), reported alongside as ``rebase_ms``.

Besides CSV rows, ``run`` writes a machine-readable perf trajectory to the
repo-level ``BENCH_decode.json`` (mode x horizon x impl -> ms/token,
bytes/token) so future PRs can diff serving perf without re-parsing CSVs.

    PYTHONPATH=src python -m benchmarks.run --only decode
    REPRO_BENCH_SMOKE=1 ... (one tiny horizon for CI)
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig, reduced
from repro.configs.registry import get_config
from repro.models.attention import _broadcast_kv
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.runtime import interpret_kernels
from repro.serve.decode import decode_step
from repro.serve.decode_state import (
    landmark_counts,
    landmark_means,
    make_rebase_fn,
    recompute_stats,
    segment_len,
)
from repro.serve.paged import BlockAllocator, PagedKVCache, ZERO_BLOCK

MODES = ("recompute", "exact", "frozen")

_cells: dict[str, dict] = {}


def _smoke() -> bool:
    return os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def _record(rows, impl, horizon, mode, metric, value):
    rows.append(f"decode,{impl}_h{horizon}_{mode},{metric},{value:.3f}")
    _cells.setdefault(f"{impl}|{mode}|{horizon}", {})[metric] = round(value, 4)


def _setup():
    # scan_layers=False: per-layer cache leaves are separate donated jit
    # arguments, so the K/V updates alias in place — a layer scan routes
    # the cache through scan outputs, which forces an O(S) copy per tick
    # that would mask the attention-cost differences this bench measures.
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-7b")), capacity_factor=100.0,
        decode_attention_impl="spectral_shift", scan_layers=False,
    )
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


@functools.partial(jax.jit, static_argnames=("cfg", "s_max", "pos"))
def _synthetic_cache(cfg, s_max: int, pos: int, key):
    """B=1 decode cache at write position ``pos+1``: random K/V, landmark
    sums consistent with them, and exact streaming stats — everything a
    decode tick reads, without paying an O(S) prefill at bench setup."""
    h, hkv, dh, c = (
        cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
        cfg.num_landmarks,
    )
    seg = segment_len(s_max, c)
    t = jnp.arange(s_max)
    t_mask = (t <= pos).astype(jnp.float32)
    oh = (
        ((t // seg)[None, :] == jnp.arange(c)[:, None]).astype(jnp.float32)
        * t_mask[None, :]
    )  # (c, S)
    counts = landmark_counts(jnp.asarray(pos), s_max, c)
    scale = dh ** -0.5

    def layer(key):
        ks = jax.random.split(key, 3)
        kk = jax.random.normal(ks[0], (1, hkv, s_max, dh)) * 0.5 * t_mask[:, None]
        vv = jax.random.normal(ks[1], (1, hkv, s_max, dh)) * t_mask[:, None]
        qq = jax.random.normal(ks[2], (1, h, s_max, dh)) * 0.5 * t_mask[:, None]
        q_lmk = jnp.einsum("cs,bhsd->bhcd", oh, qq)
        k_lmk = jnp.einsum("cs,bhsd->bhcd", oh, kk)
        kb = _broadcast_kv(kk, h)
        vb = _broadcast_kv(vv, h)
        m, l, acc = recompute_stats(
            landmark_means(q_lmk, counts), kb, vb, pos, scale,
            row_valid=counts > 0,
        )
        return {
            "k": kk, "v": vv, "q_lmk": q_lmk, "k_lmk": k_lmk,
            "bv_m": m, "bv_l": l, "bv_acc": acc,
        }

    keys = jax.random.split(key, cfg.num_layers)
    if cfg.scan_layers:
        layers = jax.vmap(layer)(keys)
    else:
        layers = [layer(k) for k in keys]
    return {"pos": jnp.asarray(pos + 1, jnp.int32), "layers": layers}


# --------------------------------------------------------------------------
# Analytic per-tick HBM-bytes accounting (per lane; the cells run 1 lane).
# --------------------------------------------------------------------------
def _tick_bytes(kv: PagedKVCache, mode: str, impl: str, nb_view: int) -> float:
    """Modelled HBM traffic of one decode tick, from the storage layout.

    seq-leaf token row = bytes of one token across a leaf's non-seq dims;
    ``view`` = nb_view blocks of that; ``block`` = one block. Both pool
    ticks additionally re-zero the reserved ZERO_BLOCK every tick (the
    inactive-lane dump target) — one more block write each.

    dense  : horizon read (mode-dependent) + in-place token write + dense 2x
    gather : 2x view (pool read + dense-view write) + horizon read +
             2x block (commit read+write) + 1x block (ZERO_BLOCK re-zero)
             + dense 2x
    paged  : horizon read via the kernel (single pool pass, exact only) +
             1x block commit + 1x block (ZERO_BLOCK re-zero) + dense 2x
    """
    seq_token = 0.0
    dense_rw = 0.0
    for arr, info in zip(kv._storage, kv.infos):
        it = arr.dtype.itemsize
        if info.seq_axis is None:
            # lane-dense leaf: per-lane slice read + write each tick
            dense_rw += 2.0 * float(np.prod(info.spec.shape)) * it
        else:
            shape = info.spec.shape
            row = float(np.prod(shape)) / shape[info.seq_axis] * it
            seq_token += row
    view = nb_view * kv.block_size * seq_token
    block = kv.block_size * seq_token
    # Horizon bytes the attention math itself reads: recompute rebuilds
    # B/BV over all K/V; exact reads them once for the active row; frozen
    # reads nothing between boundaries.
    horizon = {"recompute": view, "exact": view, "frozen": 0.0}[mode]
    if impl == "dense":
        return horizon + seq_token + dense_rw
    if impl == "gather":
        return 2.0 * view + horizon + 3.0 * block + dense_rw
    if impl == "paged":
        return horizon + 2.0 * block + dense_rw
    raise ValueError(impl)


# --------------------------------------------------------------------------
# Cells.
# --------------------------------------------------------------------------
def _dense_cell(rows, cfg, params, horizon: int, mode: str, tokens: int):
    mcfg = dataclasses.replace(cfg, decode_streaming=mode)
    seg = segment_len(horizon, mcfg.num_landmarks)
    pos0 = horizon - tokens - 2
    cache = _synthetic_cache(mcfg, horizon, pos0, jax.random.PRNGKey(1))
    step = jax.jit(
        lambda c, t: decode_step(params, mcfg, c, t), donate_argnums=(0,)
    )
    tok = jnp.ones((1, 1), jnp.int32)
    _, cache = step(cache, tok)  # compile + warmup (advances pos by 1)
    rebase_ms = 0.0
    if mode == "frozen":
        # Time the boundary-rebase program on its own; the steady-state
        # per-token cost charges one rebase per segment (seg tokens).
        rebase = jax.jit(make_rebase_fn(mcfg, horizon), donate_argnums=(0,))
        cache = rebase(cache, jnp.asarray(pos0 + 1))  # compile
        jax.block_until_ready(jax.tree.leaves(cache)[0])
        t0 = time.perf_counter()
        for _ in range(2):
            cache = rebase(cache, jnp.asarray(pos0 + 1))
        jax.block_until_ready(jax.tree.leaves(cache)[0])
        rebase_ms = (time.perf_counter() - t0) / 2 * 1e3
        _record(rows, "dense", horizon, mode, "rebase_ms", rebase_ms)
    jax.block_until_ready(jax.tree.leaves(cache)[0])
    t0 = time.perf_counter()
    for _ in range(tokens):
        logits, cache = step(cache, tok)
    jax.block_until_ready(logits)
    ms = (time.perf_counter() - t0) / tokens * 1e3 + rebase_ms / seg
    _record(rows, "dense", horizon, mode, "per_token_ms", ms)
    return ms


def _pool_cell(rows, cfg, params, horizon: int, mode: str, tokens: int,
               impl: str, cost_check: bool = False):
    """Block-pool storage cell: ``impl`` = "gather" (legacy dense-view
    tick) or "paged" (gather-free block-table kernel tick).

    ``cost_check=True`` additionally records XLA's own ``cost_analysis()``
    flops/bytes for the tick program (telemetry/accounting.py) and the
    ratio against the analytic ``_tick_bytes`` model — the cross-check is
    the RATIO's stability, not its value: XLA charges scatter/dynamic-
    update at full-operand size regardless of in-place aliasing (see the
    module docstring), so the ratio sits far above 1 by construction and a
    drift in it flags either a layout change or a cost-model change."""
    mcfg = dataclasses.replace(cfg, decode_streaming=mode)
    seg = segment_len(horizon, mcfg.num_landmarks)
    # Fixed serving-style block size across horizons: the paged tick's
    # "one block" commit term must not scale with S for the frozen-mode
    # bytes-flat claim to be a measured fact rather than a block-size
    # artifact. (Pre-PR5 CSVs used horizon//64 here.)
    block = 64
    serve = ServeConfig(max_lanes=1, max_seq=horizon, block_size=block)
    kv = PagedKVCache(mcfg, serve)
    alloc = BlockAllocator(serve.resolved_num_blocks, serve.block_size)
    pos0 = horizon - tokens - 2
    alloc.alloc(0, alloc.blocks_for_tokens(pos0 + 1))
    tables = np.full((1, serve.blocks_per_lane), ZERO_BLOCK, np.int32)
    row = alloc.tables[0]
    tables[0, : len(row)] = row
    cache = _synthetic_cache(mcfg, horizon, pos0, jax.random.PRNGKey(1))
    kv.write_prefill(0, cache, tables[0], n_tokens=pos0 + 1)
    step = functools.partial(decode_step, cfg=mcfg, seq_max=horizon)
    if impl == "paged":
        meta = (block, interpret_kernels())
        fused = kv.make_paged_step(
            lambda p, c, t, tb: step(
                p, cache=c, tokens=t, paged_table=tb, paged_meta=meta
            ),
            params,
        )
    else:
        fused = kv.make_fused_step(
            lambda p, c, t: step(p, cache=c, tokens=t), params
        )
    nb = kv.view_blocks_needed(np.asarray([horizon - 1]), [0])
    tok = np.ones((1, 1, 1), np.int32)
    active = np.asarray([True])

    def tick(pos):
        nonlocal tables
        need = pos // block
        if need >= len(alloc.tables[0]):
            alloc.alloc(0, 1)
            tables = np.full((1, serve.blocks_per_lane), ZERO_BLOCK, np.int32)
            tables[0, : len(alloc.tables[0])] = alloc.tables[0]
        logits, new_storage = fused(
            kv._storage, jnp.asarray(tables), jnp.asarray(tok),
            jnp.asarray([pos], np.int32), jnp.asarray(active), nb,
        )
        kv._storage = list(new_storage)
        return logits

    lg = tick(pos0 + 1)  # compile + warmup
    rebase_ms = 0.0
    if mode == "frozen":
        # Boundary rebase (gather route in both impls — it recomputes two
        # rows over the horizon and commits only dense stats leaves).
        rebase = kv.make_rebase_step(jax.vmap(make_rebase_fn(mcfg, horizon)))

        def run_rebase(pos):
            kv._storage = list(rebase(
                kv._storage, jnp.asarray(tables),
                jnp.asarray([pos], np.int32), jnp.asarray(active), nb,
            ))

        run_rebase(pos0 + 1)  # compile
        jax.block_until_ready(kv._storage[0])
        t0 = time.perf_counter()
        for _ in range(2):
            run_rebase(pos0 + 1)
        jax.block_until_ready(kv._storage[0])
        rebase_ms = (time.perf_counter() - t0) / 2 * 1e3
        _record(rows, impl, horizon, mode, "rebase_ms", rebase_ms)
    jax.block_until_ready(lg)
    t0 = time.perf_counter()
    for i in range(tokens):
        lg = tick(pos0 + 2 + i)
    jax.block_until_ready(lg)
    ms = (time.perf_counter() - t0) / tokens * 1e3 + rebase_ms / seg
    model_bytes = _tick_bytes(kv, mode, impl, nb)
    _record(rows, impl, horizon, mode, "per_token_ms", ms)
    _record(rows, impl, horizon, mode, "per_token_bytes", model_bytes)
    if cost_check:
        from repro.telemetry.accounting import compiled_cost

        cost = compiled_cost(
            fused._jitted, kv._storage, params, jnp.asarray(tables)[:, :nb],
            jnp.asarray(tok), jnp.asarray([pos0 + 2], np.int32),
            jnp.asarray(active),
        )
        _record(rows, impl, horizon, mode, "xla_cost_flops", cost["flops"])
        _record(rows, impl, horizon, mode, "xla_cost_bytes", cost["bytes"])
        if cost["bytes"]:
            _record(rows, impl, horizon, mode, "xla_to_model_bytes",
                    cost["bytes"] / model_bytes)
    return ms


def write_json() -> None:
    from benchmarks.run import write_bench  # lazy: avoids an import cycle

    write_bench(
        "decode",
        schema="impl|mode|horizon -> {per_token_ms, per_token_bytes, "
               "rebase_ms?, xla_cost_bytes?, xla_cost_flops?}",
        extra={"impls": {
            "dense": "lane-dense decode_step (no paging)",
            "gather": "block pools + legacy gather/scatter tick",
            "paged": "block pools + gather-free block-table kernel tick",
        }},
        cells=_cells,
    )


def run(rows: list[str]) -> None:
    _cells.clear()
    cfg, params = _setup()
    if _smoke():
        horizons, tokens = (512,), 4
    else:
        horizons, tokens = (1024, 8192, 32768), 8
    for h in horizons:
        # Cost analysis AOT-compiles each tick program a second time, so
        # only the smallest horizon pays for the cross-check.
        cost_check = h == horizons[0]
        ms = {}
        for mode in MODES:
            ms[mode] = _dense_cell(rows, cfg, params, h, mode, tokens)
        for mode in MODES:
            _pool_cell(rows, cfg, params, h, mode, tokens, "gather",
                       cost_check=cost_check)
        for mode in ("exact", "frozen"):  # recompute stays gather-only
            _pool_cell(rows, cfg, params, h, mode, tokens, "paged",
                       cost_check=cost_check)
        rows.append(
            f"decode,dense_h{h},exact_speedup_vs_recompute,"
            f"{ms['recompute'] / max(ms['exact'], 1e-9):.2f}"
        )
        rows.append(
            f"decode,dense_h{h},frozen_speedup_vs_recompute,"
            f"{ms['recompute'] / max(ms['frozen'], 1e-9):.2f}"
        )
    write_json()


if __name__ == "__main__":
    out: list[str] = []
    run(out)
    print("name,case,metric,value")
    print("\n".join(out))
