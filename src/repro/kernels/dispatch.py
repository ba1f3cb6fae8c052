"""Impl / block-size dispatch for spectral-shifting attention.

One registry answers "which implementation, which block size?" for every
attention call, replacing ad-hoc ``impl == "spectral_shift_fused"``
branching in model code:

    key  = (backend, n_bucket, c, d, dtype, causal, family, seq_shards)
    plan = Plan(impl = fused | jnp | interpret | sharded, block_n, block_c,
                source)

``family="decode"`` keys serving's single-step shape (n = cache horizon);
``seq_shards`` keys context-parallel cells, whose plans route through the
shard_map driver in ``kernels/sharded.py``.

Resolution order: in-memory registry -> on-disk autotune cache -> measured
autotune (only when explicitly enabled) -> backend heuristic. Plans are
resolved at *trace* time — shapes are static under jit, so a jitted train
step consults the registry once per compiled shape and bakes the winning
kernel in.

The measured-autotune mode times real candidate executions (jnp reference
vs fused kernels across the (block_n, block_c) grid — ``block_c`` tiles the
B-side kernel's landmark rows, see kernels/ss_attention.py) on synthetic
data of the exact shape and persists winners to a JSON cache
(``REPRO_AUTOTUNE_CACHE`` or ``<repo>/.autotune/ss_autotune.json``, inside
the checkout, so what compiles depends only on the checkout) so
subsequent processes skip the measurement. ``n`` is bucketed to the next
power of two to keep the cache dense across nearby sequence lengths.

``decode`` keys measure through their own harness (``autotune_decode``):
the gather-route jnp one-row recompute vs the gather-free paged kernel
(kernels/paged_decode.py) across the ``block_table`` view-slot-bucketing
grid at the serve shape — ``ServeEngine`` warms this key at construction,
so a tuned deployment's ticks follow the measured winner's geometry.

Cache payloads are written at version 3 (plans carry ``block_table``; v2
added ``block_c``); older caches load unchanged with the missing fields
defaulting to 0 (the former behavior).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.attention import SSConfig, spectral_shift_attention
from repro.runtime import REPO_ROOT
from repro.telemetry.metrics import RegistrySlot

_IMPLS = ("fused", "jnp", "interpret", "sharded", "paged")
_FAMILIES = ("self", "decode")

# Telemetry sink. The import-time default is the no-op registry — plan
# resolution happens at trace time on hot paths, and with telemetry off the
# counters must cost nothing. ServeEngine/Trainer install their shared
# registry via set_metrics() when ServeConfig.telemetry is enabled.
_METRICS = RegistrySlot()


def set_metrics(registry) -> None:
    """Install a metrics registry for plan-resolution counters (process-
    wide, like the plan registry itself; held weakly, see
    ``RegistrySlot``). Pass ``None`` to detach."""
    _METRICS.set(registry)


def _count_resolution(outcome: str) -> None:
    # outcome: memory|disk (cache tier hits), miss_sweep (measured
    # autotune ran), miss_heuristic (backend default used)
    _METRICS.get().counter(
        "autotune_plan_resolutions_total",
        help="get_plan outcomes by resolution tier",
        labels=("outcome",),
    ).labels(outcome=outcome).inc()


@dataclasses.dataclass(frozen=True)
class PlanKey:
    backend: str      # "cpu" | "tpu" | "gpu"
    n: int            # sequence length, bucketed to next power of two
    c: int            # landmark count
    d: int            # head dim
    dtype: str        # canonical dtype name, e.g. "float32" / "bfloat16"
    causal: bool
    family: str = "self"   # "self" = full-sequence attention; "decode" =
                           # one-step query against a cache horizon of n
    seq_shards: int = 1    # context parallelism: devices the sequence axis
                           # is sharded over (1 = single-device kernels)

    def encode(self) -> str:
        kind = "causal" if self.causal else "bidir"
        s = f"{self.backend}|n{self.n}|c{self.c}|d{self.d}|{self.dtype}|{kind}"
        if self.family != "self":
            s += f"|{self.family}"
        if self.seq_shards > 1:
            s += f"|sp{self.seq_shards}"
        return s

    @staticmethod
    def decode(s: str) -> "PlanKey":
        parts = s.split("|")
        backend, n, c, d, dtype, kind = parts[:6]
        family, seq_shards = "self", 1
        for extra in parts[6:]:  # optional suffixes; legacy keys have none
            if extra.startswith("sp"):
                seq_shards = int(extra[2:])
            elif extra in _FAMILIES:
                family = extra
            else:
                raise ValueError(f"unknown PlanKey suffix {extra!r}")
        return PlanKey(
            backend=backend, n=int(n[1:]), c=int(c[1:]), d=int(d[1:]),
            dtype=dtype, causal=(kind == "causal"), family=family,
            seq_shards=seq_shards,
        )


@dataclasses.dataclass(frozen=True)
class Plan:
    impl: str            # "fused" | "jnp" | "interpret" | "sharded" |
                         # "paged" (decode family: the gather-free
                         # block-table kernel; "jnp" = the gather route)
    block_n: int = 512
    block_c: int = 0     # landmark-row tile for the B-side kernel (0 = all
                         # rows resident; only honored when it divides c)
    block_table: int = 0  # decode family: view-slot bucketing quantum for
                          # the paged decode kernel — the engine rounds the
                          # block-table slot count (kernel grid size) up to
                          # a multiple of this instead of the next power of
                          # two (0 = power-of-two default). Trades compiled
                          # tick-program count against wasted masked grid
                          # steps.
    source: str = "heuristic"  # heuristic | registered | cache | autotuned

    def __post_init__(self):
        if self.impl not in _IMPLS:
            raise ValueError(f"unknown impl {self.impl!r}; want one of {_IMPLS}")


_lock = threading.Lock()
_REGISTRY: dict[PlanKey, Plan] = {}
_CACHE_LOADED: set[str] = set()
_CACHE_OVERRIDE: Optional[str] = None


def _bucket(n: int) -> int:
    """Next power of two >= n (min 128): nearby lengths share one plan."""
    b = 128
    while b < n:
        b *= 2
    return b


def make_key(
    n: int, c: int, d: int, dtype, causal: bool, backend: Optional[str] = None,
    family: str = "self", seq_shards: int = 1,
) -> PlanKey:
    """``family="decode"`` keys a single-step (n_q=1) query against a cache
    horizon of ``n`` tokens; ``seq_shards`` keys context-parallel cells by
    how many devices the sequence axis spans."""
    if family not in _FAMILIES:
        raise ValueError(f"unknown key family {family!r}; want one of {_FAMILIES}")
    return PlanKey(
        backend=backend or jax.default_backend(),
        n=_bucket(n),
        c=c,
        d=d,
        dtype=jnp.dtype(dtype).name,
        causal=causal,
        family=family,
        seq_shards=max(int(seq_shards), 1),
    )


def cache_path() -> str:
    if _CACHE_OVERRIDE:
        return _CACHE_OVERRIDE
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(REPO_ROOT, ".autotune", "ss_autotune.json"),
    )


def set_cache_path(path: Optional[str]) -> None:
    """Process-wide cache-file override (``ModelConfig.autotune_cache``):
    every subsequent load/save — including trace-time ``_default_tune``
    winners — round-trips through this file. ``None``/"" restores the
    env-var/default resolution."""
    global _CACHE_OVERRIDE
    _CACHE_OVERRIDE = path or None


def register_plan(key: PlanKey, plan: Plan) -> None:
    with _lock:
        _REGISTRY[key] = plan


def clear_registry() -> None:
    global _CACHE_OVERRIDE
    with _lock:
        _REGISTRY.clear()
        _CACHE_LOADED.clear()
        _CACHE_OVERRIDE = None


def load_cache(path: Optional[str] = None) -> int:
    """Merge plans from the on-disk cache into the registry; returns count."""
    path = path or cache_path()
    if not os.path.exists(path):
        return 0
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        return 0
    plans = payload.get("plans", {})
    loaded = 0
    with _lock:
        for ks, pd in plans.items():
            try:
                key = PlanKey.decode(ks)
                plan = Plan(
                    impl=pd["impl"], block_n=int(pd["block_n"]),
                    # Version-1 caches predate block_c, version <=2 predate
                    # block_table; absent means untiled / pow2-bucketed.
                    block_c=int(pd.get("block_c", 0)),
                    block_table=int(pd.get("block_table", 0)),
                    source="cache",
                )
            except (ValueError, KeyError):
                continue
            # In-process plans (registered/autotuned this run) win over disk.
            _REGISTRY.setdefault(key, plan)
            loaded += 1
        _CACHE_LOADED.add(path)
    return loaded


def save_cache(path: Optional[str] = None) -> str:
    """Write all non-heuristic registry plans to disk (atomic, merging)."""
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    existing: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f).get("plans", {})
        except (OSError, json.JSONDecodeError):
            existing = {}
    with _lock:
        for key, plan in _REGISTRY.items():
            if plan.source == "heuristic":
                continue
            existing[key.encode()] = {
                "impl": plan.impl, "block_n": plan.block_n,
                "block_c": plan.block_c, "block_table": plan.block_table,
            }
    tmp = f"{path}.tmp.{os.getpid()}"
    # Version 3: plans carry block_table (v2 added block_c). Readers accept
    # every version (missing fields default to 0), so old caches stay
    # usable in place.
    with open(tmp, "w") as f:
        json.dump({"version": 3, "plans": existing}, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def heuristic_plan(key: PlanKey) -> Plan:
    """Backend defaults when nothing measured is available."""
    if key.family == "decode":
        # "jnp" = the gather route's dense-view decode math; "paged" = the
        # gather-free block-table kernel (kernels/paged_decode.py). On a
        # real accelerator the paged kernel wins by skipping the per-tick
        # view gather; on CPU interpret-mode Pallas loses to jnp, so the
        # gather route stays the default there.
        impl = "jnp" if key.backend == "cpu" else "paged"
        return Plan(impl=impl, block_n=min(512, key.n), source="heuristic")
    if key.backend == "cpu":
        # Interpret-mode Pallas is an order of magnitude slower than the jnp
        # reference on CPU; fused only pays off on a real accelerator. Holds
        # for context-parallel cells too (the jnp route partitions via GSPMD).
        return Plan(impl="jnp", block_n=min(512, key.n), source="heuristic")
    # Block size from the PER-DEVICE stream length: under context
    # parallelism each shard streams only n / seq_shards keys.
    n_loc = max(key.n // key.seq_shards, 128)
    if n_loc <= 1024:
        block = 256
    elif n_loc <= 8192:
        block = 512
    else:
        block = 1024
    impl = "sharded" if key.seq_shards > 1 else "fused"
    return Plan(impl=impl, block_n=block, source="heuristic")


def get_plan(key: PlanKey, *, autotune_enabled: bool = False,
             tune_fn: Optional[Callable[[PlanKey], Plan]] = None) -> Plan:
    """Registry -> disk cache -> measured autotune (opt-in) -> heuristic."""
    with _lock:
        plan = _REGISTRY.get(key)
    if plan is not None:
        _count_resolution("memory")
        return plan
    if cache_path() not in _CACHE_LOADED:
        load_cache()
        with _lock:
            plan = _REGISTRY.get(key)
        if plan is not None:
            _count_resolution("disk")
            return plan
    if autotune_enabled:
        if key.seq_shards > 1:
            # Measured autotune cannot reproduce the multi-device program;
            # measuring here would register the winner under a DIFFERENT
            # key (no seq_shards) and re-run the timing sweep on every
            # trace of the requested key. Heuristics (or pre-registered
            # plans) steer context-parallel cells.
            _count_resolution("miss_heuristic")
            return heuristic_plan(key)
        _count_resolution("miss_sweep")
        if key.family == "decode":
            # Decode keys get their own harness: gather-route jnp recompute
            # vs the paged kernel across the (block_n, block_table) grid at
            # the serve shape, registered under the decode key itself.
            return (tune_fn or _default_decode_tune)(key)
        return (tune_fn or _default_tune)(key)
    _count_resolution("miss_heuristic")
    return heuristic_plan(key)


# --------------------------------------------------------------------------
# Measured autotune.
# --------------------------------------------------------------------------
def _time_call(fn, *args, reps: int = 2) -> float:
    fn(*args)  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(
    n: int,
    c: int,
    d: int,
    dtype=jnp.float32,
    causal: bool = False,
    *,
    backend: Optional[str] = None,
    block_candidates: tuple[int, ...] = (256, 512, 1024),
    block_c_candidates: Optional[tuple[int, ...]] = None,
    reps: int = 2,
    save: bool = True,
    cache_file: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Plan:
    """Measure jnp vs fused across the (block_n, block_c) candidate grid on
    synthetic data of the exact shape; register and (optionally) persist the
    winner. ``block_c_candidates`` defaults to the untiled kernel plus the
    divisor tiles c/2 and c/4 (when whole) — tiling trades smaller VMEM
    accumulators for re-streaming K/V per landmark tile."""
    from repro.kernels.ops import ss_attention_fused

    _METRICS.get().counter(
        "autotune_sweeps_total", help="measured autotune sweeps run",
        labels=("family",),
    ).labels(family="self").inc()
    key = make_key(n, c, d, dtype, causal, backend=backend)
    if interpret is None:
        interpret = key.backend == "cpu"
    if block_c_candidates is None:
        block_c_candidates = (0,) + tuple(
            c // f for f in (2, 4) if c % f == 0 and c // f >= 8
        )
    cfg = SSConfig(num_landmarks=c, causal=causal)
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = (jax.random.normal(kq, (1, n, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(kk, (1, n, d)) * 0.5).astype(dtype)
    v = jax.random.normal(kv, (1, n, d)).astype(dtype)

    # Tag the sweep so telemetry/accounting.py attributes its (expected,
    # numerous) backend compiles to "autotune_sweep" instead of whatever
    # hot-loop program the engine/trainer is currently tagged with.
    from repro.telemetry.accounting import tagged_program

    jnp_fn = jax.jit(lambda q, k, v: spectral_shift_attention(q, k, v, cfg))
    with tagged_program("autotune_sweep"):
        results: list[tuple[float, Plan]] = [
            (_time_call(jnp_fn, q, k, v, reps=reps),
             Plan(impl="jnp", block_n=min(512, n), source="autotuned"))
        ]
        fused_impl = "interpret" if interpret else "fused"
        for block in dict.fromkeys(min(bc, n) for bc in block_candidates):
            for bc_c in dict.fromkeys(block_c_candidates):
                fn = functools.partial(
                    ss_attention_fused, cfg=cfg, block_n=block, block_c=bc_c,
                    interpret=interpret,
                )
                try:
                    t = _time_call(fn, q, k, v, reps=reps)
                except Exception:
                    # the interpreter may reject a candidate geometry; a
                    # compiled kernel that fails to lower is a bug
                    if not interpret:
                        raise
                    continue
                results.append((
                    t,
                    Plan(impl=fused_impl, block_n=block, block_c=bc_c,
                         source="autotuned"),
                ))
    _, plan = min(results, key=lambda r: r[0])
    register_plan(key, plan)
    if save:
        save_cache(cache_file)
    return plan


def _default_tune(key: PlanKey) -> Plan:
    return autotune(
        key.n, key.c, key.d, dtype=key.dtype, causal=key.causal,
        backend=key.backend,
    )


def autotune_decode(
    n: int,
    c: int,
    d: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    block_size: int = 16,
    block_table_candidates: tuple[int, ...] = (0, 2, 4, 8),
    reps: int = 2,
    save: bool = True,
    cache_file: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Plan:
    """Measured autotune for the ``decode`` key family: the per-tick
    horizon read at the serve shape (cache horizon ``n``, one active row
    per kv head).

    Candidates: the gather route (assemble the dense block view, then the
    jnp one-row recompute — ``impl="jnp"``) vs the gather-free paged kernel
    (``impl="paged"``) across the ``block_table`` grid. ``block_table`` is
    the view-slot bucketing quantum (see ``Plan``); each candidate is timed
    at a mid-growth and a full view so quanta that round to larger masked
    grids pay for it honestly. The kernel's key-block size is pinned to the
    pool's ``block_size`` by the storage layout, so — unlike the self
    family — ``block_n`` has no measured dimension here; it is carried at
    the heuristic value for any blockwise gather-route scans. The winner
    registers (and persists) under the decode key itself.

    Callers must pass the deployment's real ``block_size``
    (``ServeEngine`` threads ``ServeConfig.block_size`` through its
    ``tune_fn``): ``PlanKey`` does not encode block size, so deployments
    that share a shape key but differ in block size overwrite each
    other's measured winner — last tuned wins, a deliberate granularity
    trade-off, but never measure at a geometry you don't serve."""
    from repro.kernels.paged_decode import paged_row_stats_lanes
    from repro.serve.decode_state import recompute_stats
    from repro.serve.paged import bucket_view_slots

    _METRICS.get().counter(
        "autotune_sweeps_total", help="measured autotune sweeps run",
        labels=("family",),
    ).labels(family="decode").inc()
    key = make_key(n, c, d, dtype, True, backend=backend, family="decode")
    if interpret is None:
        interpret = key.backend == "cpu"
    bs = block_size
    n_slots_full = -(-n // bs)
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = (jax.random.normal(kq, (1, 1, 1, d)) * 0.5).astype(jnp.float32)
    k_pool = (jax.random.normal(kk, (1, n_slots_full + 1, bs, d)) * 0.5).astype(dtype)
    v_pool = jax.random.normal(kv, (1, n_slots_full + 1, bs, d)).astype(dtype)
    table = jnp.arange(1, n_slots_full + 1, dtype=jnp.int32)
    views = sorted({max(n_slots_full // 2, 1), n_slots_full})
    scale = 1.0 / (d ** 0.5)

    def time_gather(nv: int) -> float:
        tb = table[:nv]

        def fn(q_, kp, vp):
            kvw = jnp.take(kp, tb, axis=1).reshape(1, 1, nv * bs, d)
            vvw = jnp.take(vp, tb, axis=1).reshape(1, 1, nv * bs, d)
            return recompute_stats(q_, kvw, vvw, nv * bs - 2, scale)

        return _time_call(jax.jit(fn), q, k_pool, v_pool, reps=reps)

    # Same compile attribution as the self-family sweep above.
    from repro.telemetry.accounting import tagged_program

    with tagged_program("autotune_sweep"):
        results: list[tuple[float, Plan]] = [(
            sum(time_gather(nv) for nv in views),
            Plan(impl="jnp", block_n=min(512, n), source="autotuned"),
        )]
        for bt in dict.fromkeys(block_table_candidates):
            t = 0.0
            try:
                for nv in views:
                    nv_r = bucket_view_slots(nv, n_slots_full, bt)
                    tb = jnp.pad(table[:nv], (0, nv_r - nv))[None]  # ZERO_BLOCK
                    kvv = jnp.asarray([nv * bs - 1], jnp.int32)

                    def fn(q_, kp, vp, tb=tb, kvv=kvv):
                        return paged_row_stats_lanes(
                            q_, (kp,), vp, tb, kvv, scale=scale, block_size=bs,
                            interpret=interpret,
                        )

                    t += _time_call(jax.jit(fn), q, k_pool, v_pool, reps=reps)
            except Exception:
                if not interpret:  # as in autotune()
                    raise
                continue
            results.append((
                t,
                Plan(impl="paged", block_n=min(512, n), block_table=bt,
                     source="autotuned"),
            ))
    _, plan = min(results, key=lambda r: r[0])
    register_plan(key, plan)
    if save:
        save_cache(cache_file)
    return plan


def _default_decode_tune(key: PlanKey) -> Plan:
    return autotune_decode(
        key.n, key.c, key.d, dtype=key.dtype, backend=key.backend,
    )


# --------------------------------------------------------------------------
# Model-facing entry point.
# --------------------------------------------------------------------------
def dispatch_ss_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: SSConfig,
    *,
    scale: Optional[float] = None,
    backend: str = "auto",
    autotune_enabled: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Route one attention call through the dispatch registry.

    ``backend``: "auto" resolves a plan per shape key; "fused" / "jnp" /
    "interpret" / "sharded" force that implementation. Shapes (..., n, d)
    with arbitrary leading dims. Fully differentiable on every route.

    Mesh-aware: when the active ``sharding_rules`` context maps the sequence
    axis onto >1 devices, the shape key carries ``seq_shards`` and every
    kernel-backed impl routes through the shard_map context-parallel driver
    (kernels/sharded.py) instead of the single-device kernels — seq-sharded
    cells keep the fused path rather than falling back to jnp.
    """
    from repro.distributed.sharding import active_seq_sharding
    from repro.kernels.ops import ss_attention_fused

    n, d = q.shape[-2], q.shape[-1]
    mesh, seq_axes, lead_axes = active_seq_sharding()
    n_shards = 1
    if seq_axes:
        for a in seq_axes:
            n_shards *= int(mesh.shape[a])
    # Sharded self-attention only: decode/cross rectangular shapes keep the
    # single-device routing (their key axis isn't the sharded one).
    sharded_site = n_shards > 1 and n == k.shape[-2]
    if backend == "auto":
        key = make_key(
            n, cfg.num_landmarks, d, q.dtype, cfg.causal,
            seq_shards=n_shards if sharded_site else 1,
        )
        plan = get_plan(key, autotune_enabled=autotune_enabled)
        impl, block_n, block_c = plan.impl, plan.block_n, plan.block_c
    elif backend in _IMPLS:
        impl, block_n, block_c = backend, 512, 0
    else:
        raise ValueError(
            f"unknown attention backend {backend!r}; want 'auto' or one of {_IMPLS}"
        )
    if impl == "paged":
        raise ValueError(
            "'paged' plans serve the decode key family (block-pool serving "
            "ticks); self-attention sites cannot route through it"
        )
    if impl == "jnp":
        return spectral_shift_attention(q, k, v, cfg, scale=scale)
    if sharded_site and impl in ("fused", "interpret", "sharded"):
        from repro.kernels.sharded import ss_attention_fused_sharded

        return ss_attention_fused_sharded(
            q, k, v, cfg, mesh=mesh, seq_axes=seq_axes, lead_axes=lead_axes,
            scale=scale, block_n=block_n,
            interpret=True if impl == "interpret" else interpret,
        )
    if impl == "sharded":
        # A sharded plan outside a seq-sharded context degenerates to the
        # single-device kernels (one shard).
        impl = "fused"
    return ss_attention_fused(
        q, k, v, cfg, scale=scale, block_n=block_n, block_c=block_c,
        interpret=True if impl == "interpret" else interpret,
    )
