"""Continuous-batching serving engine: paged KV cache + two-phase scheduler
over the spectral-shift decode path.

vLLM-style serving on top of ``decode_step``:

* a fixed pool of ``max_lanes`` decode lanes share a **block-paged KV
  cache** (serve/paged.py): K/V lives in fixed-size token blocks handed out
  by a free-list allocator, so memory tracks the working set instead of
  ``max_lanes * max_seq``; landmark running sums — the paper-technique state
  — are a fixed ``(c, d)`` summary per layer and stay dense per lane;
* requests wait in a FCFS queue and are admitted when a lane AND enough
  blocks for their prompt are available (serve/scheduler.py). If decode
  growth exhausts the pool, the youngest request is preempted (blocks
  recycled, request requeued, recompute on re-admission);
* **batched prefill** (serve/prefill.py) pushes the whole prompt through
  the model in one jitted forward pass, writing K/V straight into the
  allocated blocks and seeding the landmark sums — first-token latency is
  one tick instead of O(prompt_len) ticks of token replay;
* every engine tick advances ALL decoding lanes with one jitted batched
  step — admission/retirement never stalls other lanes;
* decode attention state is **streamed** (serve/decode_state.py): the cache
  carries per-landmark online-softmax (m, l, BV) partials that prefill
  seeds in one shot and each decode tick extends in O(c*d), instead of
  rebuilding the landmark-to-key softmax over the whole horizon per token.
  ``ModelConfig.decode_streaming`` picks exact (token-identical, one-row
  recompute per tick) / frozen (fully streamed; the engine runs a lazy
  two-row rebase program when a lane crosses a segment boundary) /
  recompute (the legacy O(c*S*d) path, kept as baseline);
* with ``ServeConfig.decode_impl="paged"`` the decode tick is **gather-
  free**: K/V stream straight from the block pools through the
  block-table-aware Pallas kernel (kernels/paged_decode.py) and the new
  token commits via a single-block scatter — frozen-mode ticks touch
  O(c*d) state plus one block, independent of the horizon. ``"gather"``
  (default) keeps the legacy dense-view tick, which also serves
  ``decode_streaming="recompute"`` and the frozen boundary rebase.

* with ``ServeConfig.chunked_prefill=True`` the engine switches to a
  **continuous-batching tick** (``_tick_chunked``): prompts prefill in
  fixed-size chunks (serve/prefill.py ``chunk_prefill``) that ride INSIDE
  the decode tick, so a long prompt never freezes decoding lanes — each
  tick dispatches the batched decode step first, then runs up to
  ``prefill_token_budget`` worth of prompt chunks while the decode program
  executes on device, and syncs once at the sample boundary. Chunk K/V
  commits incrementally into the lane's blocks; the landmark streaming
  stats carry across chunks via the flash-merge algebra, so chunked
  prefill is greedy token-identical to whole-prompt replay prefill. A
  mid-prefill lane preempted for blocks is PARKED (committed blocks kept,
  dense carry snapshotted) and resumes at the completed-chunk boundary
  instead of recomputing. ``chunked_prefill=False`` (default) keeps the
  two-phase tick below, byte for byte.

* with ``ServeConfig.prefix_cache=True`` admissions first probe a
  **content-hash prefix index** (serve/paged.py ``PrefixCache``): prompts
  are hashed block-by-block (chained digests) and a hit maps the cached
  physical blocks into the request's table with refcounts — a full-prompt
  hit restores the cached dense landmark/streaming snapshot and emits its
  first token from the cached logits (TTFT ~ one host-side attach instead
  of a prefill pass); a partial hit resumes chunked prefill at the deepest
  cached block boundary. Divergent decode writes into a shared partial
  block copy-on-write (``BlockAllocator.cow`` + ``PagedKVCache.
  copy_block``); streaming stats attach via the canonical-segmentation
  passthrough or the ``prefix_attach="recompute"`` reseed program
  (serve/decode_state.py ``reseed_streaming``).

``ServeConfig(paged=False, batched_prefill=False)`` reproduces the seed
engine (dense per-lane caches, token-replay prefill) — kept as the
benchmark/equivalence baseline. Greedy outputs are token-identical between
the two modes; for MoE families this holds in the dropless capacity regime
(capacity dropping is sequence-length dependent, so whole-prompt prefill
and token-by-token replay legitimately route differently when tokens
overflow expert capacity — same caveat as tests/test_decode.py).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.runtime import interpret_kernels
from repro.serve.chaos import ChaosInjector, EngineStalled, FaultPlan
from repro.serve.decode import decode_step
from repro.serve.paged import BlockAllocator, PagedKVCache
from repro.serve.prefill import make_prefill_fn, prefill_supported
from repro.serve.scheduler import Scheduler
from repro.telemetry.metrics import TICK_BUCKETS


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 => greedy
    # streamed-token callback: on_token(uid, token) fires as each token is
    # sampled (inside the tick, right after the sample boundary) instead of
    # the caller polling ``finished`` after drain
    on_token: Optional[object] = None
    # tick budget from submission: past it the request is terminated with
    # outcome "deadline_expired" wherever it is (queued, parked, decoding)
    # and every resource it holds is released. 0 = no deadline.
    deadline_ticks: int = 0


@dataclasses.dataclass
class _Lane:
    req: Optional[Request] = None
    prompt_left: deque = dataclasses.field(default_factory=deque)
    generated: list[int] = dataclasses.field(default_factory=list)
    next_token: int = 0
    pos: int = 0          # cache position the next decode step writes to
    prefilled_tick: int = -1  # tick of batched prefill (skip decode that tick)
    # chunked-prefill progress (continuous batching)
    prefilling: bool = False  # mid-chunked-prefill: not a decode candidate
    prefill_pos: int = 0      # prompt tokens committed so far
    chunk_idx: int = 0        # next chunk ordinal (flight lifeline labels)
    # prefix caching: dense-state snapshots captured at block-aligned chunk
    # boundaries while this lane prefills (token count -> dense_snapshot);
    # attached to the PrefixCache entry when the prefill completes
    stat_points: dict = dataclasses.field(default_factory=dict)

    @property
    def free(self) -> bool:
        return self.req is None


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_lanes: Optional[int] = None,
        max_seq: Optional[int] = None,
        eos_id: Optional[int] = None,
        seed: Optional[int] = None,
        serve: Optional[ServeConfig] = None,
        telemetry=None,
        chaos: Optional[FaultPlan] = None,
    ):
        serve = serve or ServeConfig()
        overrides = {
            k: v
            for k, v in dict(max_lanes=max_lanes, max_seq=max_seq,
                             eos_id=eos_id, seed=seed).items()
            if v is not None
        }
        if overrides:
            serve = dataclasses.replace(serve, **overrides)
        self.cfg, self.params, self.serve = cfg, params, serve
        self.max_lanes, self.max_seq = serve.max_lanes, serve.max_seq
        self.eos_id = serve.eos_id
        self.lanes = [_Lane() for _ in range(self.max_lanes)]
        self.finished: dict[int, list[int]] = {}
        self._key = jax.random.PRNGKey(serve.seed)
        self._tick = 0

        # Telemetry: one shared registry + tracer behind ServeConfig.telemetry
        # (or an externally-owned Telemetry, e.g. a benchmark's). Disabled =>
        # no-op registry/tracer — instrumentation sites still call through,
        # but nothing is recorded and no extra device programs exist. The
        # scheduler always keeps a REAL registry (its latency percentiles are
        # part of the stats() contract); it only shares ours when enabled.
        from repro.telemetry import Telemetry

        if telemetry is None:
            telemetry = Telemetry(enabled=serve.telemetry)
        self.telemetry = telemetry
        self.telemetry.stamp_provenance(cfg, serve)

        self.kv = PagedKVCache(cfg, serve)
        alloc = (
            BlockAllocator(serve.resolved_num_blocks, serve.block_size)
            if self.kv.has_paged_leaves else None
        )
        # Prefix caching rides the continuous-batching tick (partial hits
        # resume into chunked prefill at the first non-matching block), so
        # enabling it implies the chunked machinery. Needs paged seq leaves
        # (the whole point is sharing physical blocks) and a family with
        # batched prefill; silently off otherwise.
        self._prefix_enabled = (
            serve.prefix_cache and self.kv.has_paged_leaves
            and prefill_supported(cfg)
        )
        # Continuous batching: chunk size rounded up to a block multiple so
        # every non-final chunk commits whole blocks (chunk starts stay
        # block-aligned). Families without batched prefill (hybrid/ssm)
        # fall back to the two-phase replay engine.
        self._chunked = (
            serve.chunked_prefill or self._prefix_enabled
        ) and prefill_supported(cfg)
        self._chunk = min(
            -(-serve.prefill_chunk_tokens // serve.block_size)
            * serve.block_size,
            self.max_seq,
        )
        self.sched = Scheduler(
            alloc, self.max_lanes, serve.blocks_per_lane,
            registry=self.telemetry.metrics if self.telemetry.enabled else None,
            flight=self.telemetry.flight if self.telemetry.enabled else None,
            chunk_tokens=self._chunk if self._chunked else 0,
            max_queue=serve.max_queue,
        )
        self.sched.requeue_cb = self._on_preempt
        if self._chunked:
            self.sched.park_cb = self._park_lane
            self.sched.park_drop_cb = self._drop_parked
        # parked mid-prefill state: uid -> dense-leaf snapshot + progress
        self._parked: dict[int, dict] = {}
        # Prefix cache: content-hash index over the block pool. It owns the
        # allocator's eviction hook; the scheduler charges shared blocks
        # against the pool once (prefix_probe) and breaks block sharing on
        # divergent decode writes (cow_cb -> device block copy).
        self.prefix = None
        if self._prefix_enabled:
            from repro.serve.paged import PrefixCache

            self.prefix = PrefixCache(
                alloc, max_blocks=serve.prefix_cache_blocks,
                registry=(self.telemetry.metrics
                          if self.telemetry.enabled else None),
            )
            self.sched.prefix_probe = self._prefix_probe
            self.sched.cow_cb = self.kv.copy_block
            # uid -> entry soft-pinned at probe time, released on attach
            # (see _prefix_probe); at most one pin per waiting request
            self._probe_pins: dict[int, object] = {}

        # Terminal-outcome ledger: every submitted uid ends in exactly ONE
        # of finished / cancelled / rejected / deadline_expired — the chaos
        # soak's core invariant. Numerics-guard and watchdog state rides
        # next to it; counters live on the scheduler's always-real registry
        # so the recovery ladder is observable without telemetry.
        self.outcomes: dict[int, str] = {}
        self._deadlines: dict[int, int] = {}       # uid -> expiry tick
        self._guard_trips: dict[int, int] = {}     # uid -> guard hits
        self._demoted: set[int] = set()            # uids pinned to exact mode
        self._exact_step = None                    # lazy exact-mode program
        self._guard = serve.numerics_guard
        self._progress = True
        self._stall_ticks = 0
        self._wd_interventions = 0
        self._wd_fired_tick: Optional[int] = None
        reg = self.sched.registry
        self._quarantines = reg.counter(
            "numerics_quarantines_total",
            help="lanes quarantined by the numerics guard (streaming stats "
                 "rebuilt in place from cached K/V)")
        self._demotions = reg.counter(
            "numerics_demotions_total",
            help="frozen-mode lanes demoted to the exact decode program "
                 "after repeated numerics-guard trips")
        self._wd_fires = reg.counter(
            "serve_watchdog_fires_total",
            help="no-progress watchdog escalations")
        self._recovery_h = reg.histogram(
            "serve_recovery_ticks",
            help="ticks from the first watchdog intervention to restored "
                 "progress",
            buckets=TICK_BUCKETS)

        # Chaos harness (serve/chaos.py): one injector shared by every hook
        # point, so per-tick ordinals — and therefore the whole injection
        # schedule — replay exactly from (plan.seed, tick).
        self.chaos = None
        if chaos is not None:
            self.chaos = ChaosInjector(chaos, flight=self.sched.flight,
                                       registry=self.sched.registry)
            self.sched.chaos = self.chaos
            if alloc is not None:
                alloc.chaos = self.chaos
            if self.prefix is not None:
                self.prefix.chaos = self.chaos
        if self.telemetry.enabled:
            reg = self.telemetry.metrics
            self._ticks_total = reg.counter(
                "serve_ticks_total", help="engine ticks executed")
            if alloc is not None:
                # fn-gauges: evaluated only when the registry is read, so
                # the tick loop never touches them.
                reg.gauge("pool_blocks_used", fn=lambda: float(alloc.num_used),
                          help="allocated KV blocks")
                reg.gauge("pool_blocks_free", fn=lambda: float(alloc.num_free),
                          help="free KV blocks")
                reg.gauge("pool_utilization",
                          fn=lambda: alloc.num_used / max(alloc.num_blocks - 1, 1),
                          help="allocated fraction of the usable pool")
                reg.gauge("pool_fragmentation", fn=alloc.fragmentation,
                          help="1 - longest contiguous free run / free blocks")

        # Decode-tick route: "paged" = gather-free (block-table Pallas
        # kernel + single-block scatter commit); "gather" = legacy dense
        # per-lane views. recompute-mode spectral shift rebuilds the dense
        # B matrix and is only served by the gather route, so a paged
        # request falls back (surfaced in stats()["decode_impl"]). The
        # route is an EXPLICIT ServeConfig choice by contract; the decode
        # plan warmed below steers kernel geometry (block_table view
        # bucketing) and surfaces the measured gather-vs-paged winner in
        # stats() for the operator — it does not override the route.
        paged_ok = self.kv.has_paged_leaves and not (
            cfg.decode_attention_impl == "spectral_shift"
            and cfg.decode_streaming == "recompute"
        )
        self.decode_impl = (
            "paged" if serve.decode_impl == "paged" and paged_ok else "gather"
        )
        # Pages of each paged tick's view, every lane once: those the
        # lane's position reaches (the kernel streams them, and ZERO_BLOCK
        # pages to the end of the lane's last chunk) and the tail past
        # them. Host arithmetic on positions the engine holds; no sync.
        self._pages = None
        if self.telemetry.enabled and self.decode_impl == "paged":
            fam = self.telemetry.metrics.counter(
                "paged_decode_pages_total", labels=("kind",),
                help="paged decode view pages a tick, every lane once: "
                     "reached by its position (streamed) or past it (tail)")
            self._pages = (fam.labels(kind="streamed"),
                           fam.labels(kind="tail"))
        # whole decode tick (read -> step -> commit) as one XLA program
        self._fused_step = self._make_decode_step(cfg)
        self.batched = serve.batched_prefill and prefill_supported(cfg)

        # decode_streaming="frozen": the active landmark row streams with a
        # drifting mean and is rebased lazily when a lane's write position
        # crosses a segment boundary — a second jitted program (gather ->
        # two-row recompute -> commit dense stats leaves), run only on
        # boundary ticks (amortized O(c*d)/token; serve/decode_state.py).
        from repro.serve.decode_state import segment_len

        self._seg = segment_len(self.max_seq, cfg.num_landmarks)
        self._rebases = 0
        self._frozen_rebase = (
            cfg.decode_streaming == "frozen"
            and cfg.decode_attention_impl == "spectral_shift"
            and cfg.family != "ssm"
        )
        if self._frozen_rebase:
            from repro.serve.decode_state import make_rebase_fn

            self._rebase_step = self.kv.make_rebase_step(
                jax.vmap(make_rebase_fn(cfg, self.max_seq))
            )

        # Prefix-attach stat seeding. "reseg": cached stats are stored at
        # the canonical segmentation (this engine's own — every lane shares
        # segment_len(max_seq, c)), so the attach is a pure host-side
        # dense-state restore, bitwise the state a cold prefill would have
        # left; the re-segmentation program (decode_state.resegment_sums)
        # only runs when segmentations differ, which cannot happen within
        # one engine. "recompute": dispatch the reseed program on every
        # attach — re-derive all (m, l, acc) rows exactly from the shared
        # K/V blocks through the rebase-step plumbing (the correctness
        # fallback, token-identity-tested against cold prefill).
        self._reseed_step = None
        self._can_reseed = (
            cfg.decode_attention_impl == "spectral_shift"
            and cfg.decode_streaming in ("exact", "frozen")
            and cfg.family != "ssm"
        )
        if (self._prefix_enabled and serve.prefix_attach == "recompute"
                and self._can_reseed):
            from repro.serve.decode_state import make_reseed_fn

            self._reseed_step = self.kv.make_rebase_step(
                jax.vmap(make_reseed_fn(cfg, self.max_seq))
            )

        # Online approximation monitors (telemetry only): locate the
        # streaming-stat leaves in the flat storage once, then per-rebase
        # drift probes (pre/post leaf snapshot, O(c*d) host math) and a
        # landmark-mass spectrum EMA observed at rebases and retirements.
        # _stream_idx is needed beyond telemetry now: the numerics guard
        # scans (and the chaos nan_stats site poisons) the streaming-stat
        # leaves whenever the decode state streams; the monitors themselves
        # stay telemetry-gated.
        self._stream_idx = None
        self._drift_mon = self._spectrum_mon = None
        if self._can_reseed:  # exact/frozen spectral shift: stats stream
            from repro.serve.kv_cache import stream_leaf_indices

            idx = stream_leaf_indices(cfg, self.max_seq)
            if idx["bv_m"]:
                self._stream_idx = list(
                    zip(idx["bv_m"], idx["bv_l"], idx["bv_acc"])
                )
        if self.telemetry.enabled and self._stream_idx:
            from repro.telemetry import DriftMonitor, SpectrumMonitor

            self._spectrum_mon = SpectrumMonitor(self.telemetry.metrics)
            if self._frozen_rebase:
                self._drift_mon = DriftMonitor(self.telemetry.metrics)

        # Warm the dispatch registry for the serving shapes: the decode key
        # family (n=1 step against the max_seq cache horizon) plus, for
        # ss_fused prefill, the full-sequence key whose plan picks the
        # Pallas stream block size. Resolution loads the on-disk autotune
        # cache — honoring the ModelConfig.autotune_cache override, like
        # the Trainer does — so a tuned serving deployment skips the
        # heuristics; with ModelConfig.autotune=True an unseen decode key
        # runs the measured gather-vs-paged sweep here, once, and the tick
        # programs bake in the winner's block_table view bucketing.
        from repro.kernels import dispatch

        if self.telemetry.enabled:
            # Process-wide (like the plan registry): warmup below counts too.
            dispatch.set_metrics(self.telemetry.metrics)
        if cfg.autotune_cache:
            dispatch.set_cache_path(cfg.autotune_cache)
            dispatch.load_cache()
        def _tune_decode(key):
            # Measure at THIS deployment's block size (the kernel's key
            # block is the storage block); autotune_decode's default would
            # time a different grid geometry than the real tick runs.
            return dispatch.autotune_decode(
                key.n, key.c, key.d, dtype=key.dtype, backend=key.backend,
                block_size=serve.block_size,
            )

        self.decode_plan = dispatch.get_plan(dispatch.make_key(
            self.max_seq, cfg.num_landmarks, cfg.resolved_head_dim,
            cfg.compute_dtype, True, family="decode",
        ), autotune_enabled=cfg.autotune, tune_fn=_tune_decode)
        # View-slot bucketing quantum for paged tick programs (0 = the
        # power-of-two default in view_blocks_needed).
        self._view_quantum = (
            self.decode_plan.block_table if self.decode_impl == "paged" else 0
        )
        prefill_block = 512
        if self.batched and serve.prefill_impl == "ss_fused":
            plan = dispatch.get_plan(dispatch.make_key(
                self.max_seq, cfg.num_landmarks, cfg.resolved_head_dim,
                cfg.compute_dtype, False,
            ))
            prefill_block = plan.block_n
        if self.batched:
            self._prefill = make_prefill_fn(
                params, cfg, seq_max=self.max_seq,
                prefill_impl=serve.prefill_impl, block_n=prefill_block,
            )
        if self._chunked:
            from repro.serve.prefill import make_chunk_prefill_fn

            self._chunk_step = self.kv.make_chunk_step(
                make_chunk_prefill_fn(
                    cfg, seq_max=self.max_seq,
                    stats_impl=serve.prefill_impl, block_n=prefill_block,
                ),
                self._chunk, params,
            )
        # bucket rounded up to a block multiple so prefill writes whole blocks
        b = serve.prefill_bucket
        self._bucket = -(-b // serve.block_size) * serve.block_size

        # XLA program accounting (telemetry/accounting.py): the three
        # hot-loop programs are wrapped so every jit cache miss increments
        # xla_compiles_total{program=} — a steady-state engine must show
        # the counter FLAT across ticks (shape-bucket explosions show up
        # immediately). The jax.monitoring listener additionally attributes
        # backend compiles we don't wrap (autotune sweeps) to their tagged
        # region. Numerics probes are a separate knob: they force a host
        # sync, so ServeConfig.numerics_probe_every gates their cadence.
        from repro.telemetry import accounting as acct

        self._numerics = acct.NullNumericsProbe()
        if self.telemetry.enabled:
            acct.set_metrics(self.telemetry.metrics)
            acct.install_compile_listener()
            self._acct = acct.XLAAccounting(self.telemetry.metrics,
                                            self.telemetry.tracer)
            self._fused_step = self._acct.wrap(self._fused_step, "decode_tick")
            if self.batched:
                self._prefill = self._acct.wrap(self._prefill, "prefill")
            if self._chunked:
                self._chunk_step = self._acct.wrap(
                    self._chunk_step, "prefill_chunk"
                )
            if self._frozen_rebase:
                self._rebase_step = self._acct.wrap(self._rebase_step, "rebase")
            if self._reseed_step is not None:
                self._reseed_step = self._acct.wrap(
                    self._reseed_step, "prefix_attach"
                )
            if serve.numerics_probe_every > 0:
                self._numerics = acct.NumericsProbe(self.telemetry.metrics)
        else:
            self._acct = None

    # -- public API ----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request. Returns False when the ``ServeConfig.max_queue``
        admission bound rejects it (outcome "rejected"; the flight event
        carries a retry-after hint) — callers without backpressure
        handling can ignore the return value, as max_queue=0 never
        rejects."""
        if len(req.prompt) >= self.max_seq:
            raise ValueError(
                f"prompt len {len(req.prompt)} >= max_seq {self.max_seq}"
            )
        if not self.sched.submit(req):
            self.outcomes[req.uid] = "rejected"
            return False
        self.outcomes.pop(req.uid, None)  # resubmit sheds a stale outcome
        if req.deadline_ticks > 0:
            self._deadlines[req.uid] = self._tick + req.deadline_ticks
        return True

    def cancel(self, uid: int) -> bool:
        """Client cancellation: terminate ``uid`` wherever it is — queued,
        parked mid-prefill, or decoding — releasing its blocks, prefix
        pins, and parked snapshots. Returns False for an unknown or
        already-terminal uid."""
        return self._terminalize(uid, "cancelled")

    def _expire_deadlines(self) -> None:
        if not self._deadlines:
            return
        expired = [u for u, d in self._deadlines.items() if self._tick > d]
        for uid in expired:
            self._terminalize(uid, "deadline_expired")

    def _terminalize(self, uid: int, outcome: str) -> bool:
        """Shared cancel/deadline exit path. Every resource class a request
        can hold is released here: waiting-queue slot, scheduler parked
        entry + allocator blocks (parked uids sit in BOTH — preemption
        parks the blocks and requeues the Request), engine parked snapshot,
        prefix probe pin, guard state, lane seat."""
        self._deadlines.pop(uid, None)
        if uid in self.outcomes or uid in self.finished:
            return False
        req = self.sched.remove_waiting(uid)
        if req is not None:
            self.sched.parked.pop(uid, None)
            self._parked.pop(uid, None)
            if self.sched.allocator is not None:
                self.sched.allocator.free(uid)
            if self.prefix is not None:
                pinned = self._probe_pins.pop(uid, None)
                if pinned is not None:
                    self.prefix.unpin(pinned)
            self.sched.mark_terminal(uid, outcome)
        else:
            seat = next(
                (i for i, l in enumerate(self.lanes)
                 if l.req is not None and l.req.uid == uid), None,
            )
            if seat is None:
                return False
            self.sched.discard(seat, outcome)
            self.lanes[seat] = _Lane()
        self.outcomes[uid] = outcome
        self._guard_trips.pop(uid, None)
        self._demoted.discard(uid)
        return True

    def run(self, max_ticks: int = 10_000) -> dict[int, list[int]]:
        """Drive until queue + lanes drain (or tick budget). Returns outputs."""
        for _ in range(max_ticks):
            if self.sched.idle:
                break
            self.tick()
        return self.finished

    # -- scheduling hooks ------------------------------------------------------
    def _on_preempt(self, lane_idx: int) -> Optional[Request]:
        lane = self.lanes[lane_idx]
        req = lane.req
        self.lanes[lane_idx] = _Lane()
        return req

    def _park_lane(self, lane_idx: int) -> bool:
        """Scheduler park hook: a preemption victim caught mid-chunked-
        prefill with committed chunks keeps its blocks; only the carried
        dense state (landmark sums, streaming stats) needs saving — host
        copies, so re-admission restores without recomputing the chunks.
        Lane-dense caches can't park (the lane's seq rows get reused), so
        they fall back to full recompute."""
        lane = self.lanes[lane_idx]
        if (lane.req is None or not lane.prefilling
                or lane.prefill_pos <= 0 or not self.kv.paged):
            return False
        self._parked[lane.req.uid] = {
            "snap": self.kv.dense_snapshot(lane_idx),
            "prefill_pos": lane.prefill_pos,
            "chunk_idx": lane.chunk_idx,
        }
        return True

    def _drop_parked(self, uid: int) -> None:
        """Scheduler reclaimed a parked request's blocks: drop the resume
        snapshot; re-admission recomputes from the first chunk."""
        self._parked.pop(uid, None)

    # -- prefix caching --------------------------------------------------------
    def _plan_attach(self, req: Request):
        """Match ``req.prompt`` against the prefix index and pick the attach
        point. Returns ``(entry, n_tokens, full)`` — share the blocks
        covering the first ``n_tokens`` prompt tokens; ``full`` means the
        whole prompt (cached logits emit the first token with zero prefill
        work), otherwise ``n_tokens`` is a block-aligned stat-point boundary
        and chunked prefill resumes there. None = no usable cached state (a
        match without a snapshot at a usable boundary is still a miss).
        Parked requests resume their own committed blocks instead."""
        if (self.prefix is None or req.uid in self.sched.parked
                or req.uid in self._parked):
            return None
        m = self.prefix.match(req.prompt)
        if m is None:
            return None
        entry, k = m
        bs = self.serve.block_size
        n = len(req.prompt)
        if self.prefix.is_full_hit(entry, req.prompt, k):
            if n in entry.stat_points:
                return entry, n, True
        # Partial hit: resume chunked prefill at the deepest block-aligned
        # snapshot within the matched span. Capped at n-1 so at least one
        # token remains to prefill (the resumed tail produces the
        # first-token logits; a boundary AT n without cached logits is
        # unusable as "full").
        cap = min(k * bs, n - 1)
        best = max(
            (p for p in entry.stat_points if 0 < p <= cap and p % bs == 0),
            default=0,
        )
        if best:
            return entry, best, False
        return None

    def _prefix_probe(self, req: Request) -> int:
        """Scheduler hook: leading prompt tokens a cached prefix will cover
        at admission (0 = cold), so admission charges the tail only.

        The matched entry is soft-pinned (LRU-bumped, last in eviction
        order) until ``_try_attach_prefix`` releases it: between probe and
        attach the entry is still cache-only (no table references it yet),
        so this admission's own tail alloc — or a later admission's in the
        same tick — could otherwise reclaim it, silently turning the
        tail-only-charged hit into a cold miss. The pin rides across ticks
        while the request waits at the queue head and is re-pointed if a
        re-probe matches a different entry."""
        plan = self._plan_attach(req)
        entry = plan[0] if plan is not None else None
        prev = self._probe_pins.pop(req.uid, None)
        if prev is not None and prev is not entry:
            self.prefix.unpin(prev)
        if entry is not None:
            if prev is entry:
                self.prefix.touch(entry)
            else:
                self.prefix.pin(entry)
            self._probe_pins[req.uid] = entry
        return plan[1] if plan is not None else 0

    def _try_attach_prefix(self, i: int, req: Request) -> bool:
        """Admission-time hit detection + attach. On a hit: map the shared
        blocks into the request's table (refcounted — the tail the
        scheduler allocated at admission stays appended after them),
        restore the cached dense snapshot into the lane, and either emit
        the first token straight from the cached logits (full hit: TTFT is
        one host-side attach, no prefill pass) or resume chunked prefill at
        the boundary (partial hit). Returns True when attached."""
        pinned = self._probe_pins.pop(req.uid, None)
        if pinned is not None:
            # The admission window is over; nothing can evict the entry
            # between here and attach_shared (pure host code, no allocs),
            # and the attach itself adds a table reference.
            self.prefix.unpin(pinned)
        plan = self._plan_attach(req)
        if plan is None:
            if self.prefix is not None:
                self.prefix.note_miss()
            return False
        entry, n_attach, full = plan
        bs = self.serve.block_size
        nb = -(-n_attach // bs) if full else n_attach // bs
        blocks = entry.blocks[:nb]
        self.sched.allocator.attach_shared(req.uid, blocks)
        self.kv.dense_restore(i, entry.stat_points[n_attach])
        lane = self.lanes[i]
        # Boundary snapshots up to the attach point are valid for this
        # prompt too (same tokens): carry them so this request's completed
        # prefill can cache a deeper entry without recapturing them.
        lane.stat_points = {
            p: s for p, s in entry.stat_points.items() if p <= n_attach
        }
        if full:
            lane.pos = n_attach
            lane.prefilled_tick = self._tick
        else:
            lane.prefill_pos = n_attach
            lane.prefilling = True
        self.prefix.note_hit(entry, len(blocks))
        self.sched.mark_prefix_hit(req.uid)
        self.telemetry.flight.record(
            req.uid, "prefix_attach", tick=self._tick, lane=i,
            blocks=len(blocks), tokens=n_attach,
            mode="full" if full else "partial",
        )
        if self._reseed_step is not None:
            # "recompute" attach: re-derive the streaming stats from the
            # shared K/V instead of trusting the snapshot's (m, l, acc).
            self._run_reseed(i, n_attach - 1)
        if full:
            self._emit_token(i, np.asarray(entry.logits, np.float32))
        return True

    def _run_reseed(self, i: int, last_pos: int) -> None:
        """Dispatch the attach-reseed program for one lane (gather shared
        blocks -> recompute every reached stats row -> commit dense)."""
        positions = np.zeros(self.max_lanes, np.int32)
        flags = np.zeros(self.max_lanes, bool)
        positions[i] = last_pos
        flags[i] = True
        tables = self.sched.tables()
        nb_view = self.kv.view_blocks_needed(positions, [i])
        self.kv._storage = list(self._reseed_step(
            self.kv._storage, jnp.asarray(tables), jnp.asarray(positions),
            jnp.asarray(flags), nb_view,
        ))

    def _maybe_cache_prefix(self, i: int, logits: np.ndarray) -> None:
        """Completed-prefill hook: capture the final stat point (the lane's
        dense state at exactly ``len(prompt)`` tokens, which a full hit
        restores) and insert the prompt into the prefix index. The entry
        takes its own block references, so retirement's ``free(uid)`` keeps
        the blocks resident for future hits. No-op when every boundary is
        already cached (first entry wins)."""
        lane = self.lanes[i]
        req = lane.req
        if (self.prefix is None or req is None
                or len(req.prompt) < self.serve.block_size):
            return
        lane.stat_points[len(req.prompt)] = self.kv.dense_snapshot(i)
        self.prefix.insert(
            req.prompt, self.sched.allocator.tables.get(req.uid, []),
            stat_points=lane.stat_points, logits=logits,
        )

    def _retire(self, i: int) -> None:
        lane = self.lanes[i]
        if self._spectrum_mon is not None and lane.pos > 0:
            # Final landmark-mass concentration of the finished request —
            # the online spectrum-decay proxy (telemetry only). It reads
            # m and l alone, so acc stays on the device.
            with self.telemetry.span("spectrum_probe"):
                stats = self._lane_stream_stats(i, acc=False)
                self._spectrum_mon.observe(
                    np.stack([m for m, _ in stats]),
                    np.stack([l for _, l in stats]),
                    min((lane.pos - 1) // self._seg + 1,
                        self.cfg.num_landmarks),
                )
        uid = lane.req.uid
        self.finished[uid] = list(lane.generated)
        self.outcomes[uid] = "finished"
        self._deadlines.pop(uid, None)
        self._guard_trips.pop(uid, None)
        self._demoted.discard(uid)
        self.sched.release(i)
        self.lanes[i] = _Lane()

    # -- prefill phase ---------------------------------------------------------
    def _run_prefill(self, i: int, req: Request) -> None:
        lane = self.lanes[i]
        n = len(req.prompt)
        if (self.serve.prefill_impl == "ss_fused"
                and n <= self.cfg.num_landmarks):
            # Degenerate tiny prompt: the exact-attention path has no
            # key-validity mask, so run unpadded (cheap recompiles; the
            # kernels assert-guard padded callers).
            n_pad = n
        else:
            # Bucketed padding in both modes; ss_fused masks the pad out of
            # the softmax via the dynamic kv_valid bound.
            n_pad = min(-(-n // self._bucket) * self._bucket, self.max_seq)
        tokens = np.zeros((1, n_pad), np.int32)
        tokens[0, :n] = req.prompt
        self.telemetry.flight.record(
            req.uid, "prefill_start", bucket=n_pad, lane=i, tick=self._tick
        )
        logits, pcache = self._prefill(
            jnp.asarray(tokens), jnp.asarray(n, jnp.int32)
        )
        self.kv.write_prefill(i, pcache, self.sched.table_row(i), n_tokens=n)
        lane.pos = n
        lane.prefilled_tick = self._tick
        lg = np.asarray(logits[0, n - 1, : self.cfg.vocab_size], np.float32)
        self.telemetry.flight.record(req.uid, "prefill_end", bucket=n_pad)
        self._emit_token(i, lg)

    # -- sampling / retirement -------------------------------------------------
    def _sample(self, lane: _Lane, lg: np.ndarray) -> int:
        if lane.req.temperature > 0:
            self._key, sub = jax.random.split(self._key)
            gumbel = np.asarray(jax.random.gumbel(sub, lg.shape))
            return int(np.argmax(lg / lane.req.temperature + gumbel))
        return int(np.argmax(lg))

    def _emit_token(self, i: int, lg: np.ndarray) -> None:
        lane = self.lanes[i]
        tok = self._sample(lane, lg)
        lane.generated.append(tok)
        self._progress = True
        self.sched.note_token(lane.req.uid)
        if lane.req.on_token is not None:
            lane.req.on_token(lane.req.uid, tok)
            if self.lanes[i] is not lane:
                return  # the callback cancelled this very request
        done = (
            tok == self.eos_id
            or len(lane.generated) >= lane.req.max_new_tokens
            or lane.pos + 1 >= self.max_seq
        )
        if done:
            self._retire(i)
        else:
            lane.next_token = tok

    # -- decode dispatch (normal + demoted lanes) ------------------------------
    def _dispatch_decode(self, active: list[int]) -> list[tuple]:
        """Launch the decode program(s) for ``active`` without syncing.
        Lanes demoted by the numerics guard run on the lazily built
        exact-mode program as a second dispatch over the same (donated)
        storage; with no demotions this is exactly the single legacy call.
        Returns ``[(device_logits, lanes)]`` for ``_sync_rows`` and
        ``_merge_logits``."""
        tables = self.sched.tables()
        if self._demoted:
            normal = [i for i in active
                      if self.lanes[i].req.uid not in self._demoted]
            demoted = [i for i in active
                       if self.lanes[i].req.uid in self._demoted]
        else:
            normal, demoted = active, []
        groups = [(self._fused_step, normal)]
        if demoted:
            self._ensure_exact_step()
            groups.append((self._exact_step, demoted))
        parts = []
        idle = self.max_lanes - len(active)   # lanes no launch decodes
        for step_fn, group in groups:
            if not group:
                continue
            tokens = np.zeros((self.max_lanes, 1, 1), np.int32)
            positions = np.zeros(self.max_lanes, np.int32)
            mask = np.zeros(self.max_lanes, bool)
            for i in group:
                tokens[i, 0, 0] = self.lanes[i].next_token
                positions[i] = self.lanes[i].pos
                mask[i] = True
            nb_view = self.kv.view_blocks_needed(
                positions, group, quantum=self._view_quantum
            )
            if self._pages is not None:
                # each lane against the view of the launch that decodes
                # it; the idle lanes against the first launch's
                bs = self.serve.block_size
                reached = sum(-(-int(positions[i]) // bs) for i in group)
                self._pages[0].inc(reached)
                self._pages[1].inc((len(group) + idle) * nb_view - reached)
                idle = 0
            dev, new_storage = step_fn(
                self.kv._storage, jnp.asarray(tables), jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(mask), nb_view,
            )
            self.kv._storage = list(new_storage)
            parts.append((dev, group))
        return parts

    def _sync_rows(self, rows: list) -> list[np.ndarray]:
        """The host side of a tick's device sync, in two spans:
        ``device_wait`` until the programs and the slices ``rows`` of
        their logits are done on the device, then ``logits_fetch``, the
        copy to the host and the cast to float32."""
        tel = self.telemetry
        with tel.span("device_wait"):
            rows = jax.block_until_ready(rows)
        with tel.span("logits_fetch"):
            return [np.asarray(r, np.float32) for r in rows]

    @staticmethod
    def _merge_logits(parts: list[tuple],
                      hosts: list[np.ndarray]) -> Optional[np.ndarray]:
        """The host rows of the dispatched decode parts (``_sync_rows`` of
        their ``[:, 0, 0]`` slices) as one (max_lanes, vocab) array, each
        lane from the part that decoded it. None when nothing decoded this
        tick."""
        if not parts:
            return None
        if len(parts) == 1:
            return hosts[0]
        out = np.zeros_like(hosts[0])
        for host, (_, group) in zip(hosts, parts):
            out[group] = host[group]
        return out

    def _make_decode_step(self, cfg: ModelConfig):
        """The decode-tick program on this engine's route; the landmark
        horizon is pinned to max_seq regardless of view length."""
        step = functools.partial(decode_step, cfg=cfg, seq_max=self.max_seq)
        if self.decode_impl == "paged":
            meta = (self.serve.block_size, interpret_kernels())
            return self.kv.make_paged_step(
                lambda params, cache, tokens, table: step(
                    params, cache=cache, tokens=tokens, paged_table=table,
                    paged_meta=meta,
                ),
                self.params,
            )
        return self.kv.make_fused_step(
            lambda params, cache, tokens: step(
                params, cache=cache, tokens=tokens
            ),
            self.params,
        )

    def _ensure_exact_step(self) -> None:
        """Build the exact-mode decode program for demoted lanes. The
        storage layout is shared (exact and frozen stream the same (m, l,
        acc) leaves; exact recomputes the active row per tick instead of
        drifting it), so demoted and normal lanes ride the same pools."""
        if self._exact_step is not None:
            return
        fn = self._make_decode_step(
            dataclasses.replace(self.cfg, decode_streaming="exact")
        )
        if self._acct is not None:
            fn = self._acct.wrap(fn, "decode_exact")
        self._exact_step = fn

    def _ensure_reseed_step(self) -> bool:
        """Lazily build the stats-reseed program for the numerics guard
        (shared with the prefix_attach="recompute" path when that already
        built it)."""
        if self._reseed_step is not None:
            return True
        if not self._can_reseed:
            return False
        from repro.serve.decode_state import make_reseed_fn

        fn = self.kv.make_rebase_step(
            jax.vmap(make_reseed_fn(self.cfg, self.max_seq))
        )
        if self._acct is not None:
            fn = self._acct.wrap(fn, "prefix_attach")
        self._reseed_step = fn
        return True

    # -- chaos application & numerics-guard escalation -------------------------
    def _apply_tick_chaos(self) -> None:
        """Tick-scoped chaos sites, evaluated once per tick at the top."""
        ch = self.chaos
        rule = ch.fire("tick_delay")
        if rule is not None:
            time.sleep(rule.param or 1e-3)
        rule = ch.fire("fragment")
        if rule is not None and self.sched.allocator is not None:
            self.sched.allocator.scramble_free(ch.plan.seed + self._tick)
        rule = ch.fire("evict_storm")
        if rule is not None and self.prefix is not None:
            for _ in range(int(rule.param) or 4):
                if not self.prefix.evict_one():
                    break

    def _apply_decode_chaos(self, active: list[int],
                            logits: np.ndarray) -> None:
        """Post-step corruption sites: poison a lane's streaming stats on
        device and/or its host logits row. Runs before the guard scan, so
        the same tick detects what it injected."""
        ch = self.chaos
        for i in active:
            if self.lanes[i].free:
                continue
            if (self._stream_idx
                    and ch.fire("nan_stats", lane=i) is not None):
                s = self.kv._storage
                for im, il, ia in self._stream_idx:
                    s[im] = s[im].at[i].set(jnp.nan)
                    s[il] = s[il].at[i].set(jnp.nan)
                    s[ia] = s[ia].at[i].set(jnp.nan)
            if ch.fire("nan_logits", lane=i) is not None:
                logits[i, : self.cfg.vocab_size] = np.nan

    def _post_decode_checks(self, active: list[int],
                            logits: Optional[np.ndarray]):
        """Post-sync, pre-emit: numerics probe cadence, chaos corruption
        injection, numerics-guard escalation. Returns the (possibly
        copied-for-writability) logits."""
        probe_every = self.serve.numerics_probe_every
        if (probe_every > 0 and self._tick % probe_every == 0
                and self.telemetry.enabled):
            if logits is not None:
                self._numerics.check("decode_logits", logits)
            if self._stream_idx:
                for i in active:
                    for m, l in self._lane_stream_stats(i, acc=False):
                        self._numerics.check("landmark_m", m)
                        self._numerics.check("landmark_l", l)
        if logits is None:
            return None
        if self.chaos is not None:
            if not logits.flags.writeable:
                logits = logits.copy()
            self._apply_decode_chaos(active, logits)
        if self._guard:
            self._guard_scan(active, logits)
        return logits

    def _guard_scan(self, active: list[int], logits: np.ndarray) -> None:
        """Numerics-guard escalation ladder (ServeConfig.numerics_guard).

        Detection is host-side and NaN-keyed for the stats (the online-
        softmax ``m`` legitimately holds -inf for unreached landmark rows);
        logits must be fully finite. Recovery: stats-only corruption (K/V
        and this tick's logits intact) quarantines the lane — every (m, l,
        acc) row is rebuilt exactly from cached K/V via the reseed program
        — and the emit proceeds; corrupted logits replay-preempt the lane
        (the per-tick landmark-sum updates make an in-place retry unsound,
        so recompute is the only exact recovery). After
        ``numerics_demote_after`` trips a frozen-mode request is demoted to
        the exact-mode decode program for the rest of its life."""
        for i in active:
            lane = self.lanes[i]
            if lane.free:
                continue
            uid = lane.req.uid
            row = logits[i, : self.cfg.vocab_size]
            bad_logits = not bool(np.isfinite(row).all())
            bad_stats = False
            if not bad_logits and self._stream_idx:
                for m, l, acc in self._lane_stream_stats(i):
                    if (np.isnan(m).any() or np.isnan(l).any()
                            or np.isnan(acc).any()):
                        bad_stats = True
                        break
            if not (bad_logits or bad_stats):
                continue
            trips = self._guard_trips.get(uid, 0) + 1
            self._guard_trips[uid] = trips
            if bad_stats and self._ensure_reseed_step():
                self._quarantines.inc()
                self.sched.flight.record(uid, "quarantine", tick=self._tick,
                                         lane=i, trips=trips)
                # lane.pos is still the position this tick's step wrote
                # (the emit loop increments it after the guard).
                self._run_reseed(i, lane.pos)
            else:
                self.sched.preempt(i)
            if (trips >= self.serve.numerics_demote_after
                    and self.cfg.decode_streaming == "frozen"
                    and uid not in self._demoted):
                self._demoted.add(uid)
                self._demotions.inc()
                self.sched.flight.record(uid, "demote", tick=self._tick,
                                         trips=trips)

    # -- no-progress watchdog --------------------------------------------------
    def _watchdog_check(self) -> None:
        """Generalized livelock defense (ServeConfig.watchdog_ticks): after
        N consecutive ticks with work pending but zero progress (no token,
        no chunk, no admission), escalate one rung per tick — reclaim
        parked blocks, then preempt the youngest lane (a parked victim's
        blocks fall to the next rung) — and raise a structured
        EngineStalled only when the ladder is exhausted."""
        wd = self.serve.watchdog_ticks
        if wd <= 0:
            return
        if self._progress or self.sched.idle:
            if self._wd_fired_tick is not None:
                self._recovery_h.observe(self._tick - self._wd_fired_tick)
                self._wd_fired_tick = None
            self._stall_ticks = 0
            self._wd_interventions = 0
            return
        self._stall_ticks += 1
        if self._stall_ticks < wd:
            return
        self._wd_fires.inc()
        self.sched.flight.record(-1, "watchdog", tick=self._tick,
                                 stall_ticks=self._stall_ticks,
                                 rung=self._wd_interventions)
        if self._wd_fired_tick is None:
            self._wd_fired_tick = self._tick
        self._wd_interventions += 1
        # Interventions are bounded: each one either frees blocks or
        # empties a lane, so needing more than one full sweep of both
        # ladders means the stall is structural — stop escalating and
        # report.
        if self._wd_interventions <= 2 * (self.max_lanes + 1):
            if self.sched.reclaim_parked():
                return
            victim = self.sched._youngest_lane()
            if victim is not None:
                self.sched.preempt(victim)
                return
        alloc = self.sched.allocator
        raise EngineStalled(
            tick=self._tick, stall_ticks=self._stall_ticks,
            waiting=len(self.sched.waiting),
            active_lanes=sum(u is not None for u in self.sched.lane_uid),
            parked=len(self.sched.parked),
            pool={} if alloc is None else alloc.stats(),
        )

    # -- one engine tick -------------------------------------------------------
    def tick(self) -> None:
        with self.telemetry.span("serve_tick"):
            self._progress = False
            self._tick_inner()
            self._watchdog_check()

    def _begin_tick(self) -> None:
        """Shared tick preamble: advance the clock, evaluate the tick-
        scoped chaos sites, expire deadlines."""
        self._tick += 1
        self.sched.tick_now = self._tick
        if self.chaos is not None:
            self.chaos.begin_tick(self._tick)
            self._apply_tick_chaos()
        self._expire_deadlines()

    def _tick_inner(self) -> None:
        if self._chunked:
            return self._tick_chunked()
        self._begin_tick()
        tel = self.telemetry
        if tel.enabled:
            self._ticks_total.inc()
            # Counter-track samples for the Perfetto export: one point per
            # tick into fixed-size deques (telemetry/flight.py).
            fl = tel.flight
            fl.counter_sample("queue_depth", len(self.sched.waiting))
            alloc = self.sched.allocator
            if alloc is not None:
                fl.counter_sample("pool_blocks_used", alloc.num_used)
                fl.counter_sample("pool_fragmentation", alloc.fragmentation())

        with tel.span("admit"):
            admissions = self.sched.admit()
        if admissions:
            self._progress = True
        for i, req in admissions:
            lane = self.lanes[i] = _Lane(req=req)
            if self.batched and req.prompt:
                # prefill overwrites every dense leaf for the lane; no
                # separate zeroing needed
                with tel.span("prefill", lane=i):
                    self._run_prefill(i, req)
            else:
                self.kv.zero_lane_dense(i)
                lane.prompt_left = deque(req.prompt)
                lane.generated = []
                lane.pos = 0
                lane.next_token = (
                    lane.prompt_left.popleft() if lane.prompt_left else 0
                )

        # decode phase: every occupied lane not prefilled this very tick
        candidates = [
            i for i, l in enumerate(self.lanes)
            if not l.free and l.prefilled_tick != self._tick
        ]
        # grow block tables (may preempt — youngest first); a lane whose own
        # request was preempted (or that cannot grow) drops out of the step
        active = []
        for i in candidates:
            if self.lanes[i].free:  # preempted as a victim earlier this loop
                continue
            if not self.sched.ensure_block(i, self.lanes[i].pos):
                continue
            active.append(i)
        active = [i for i in active if not self.lanes[i].free]
        if not active:
            return

        # The tick is ONE donated XLA program (gather -> step -> commit), so
        # host spans can only split dispatch from the device sync, and the
        # sync into the wait for the device and the logits transfer; use
        # Tracer(annotate=True) + jax.profiler for phase-level device
        # timing.
        with tel.span("decode_dispatch", lanes=len(active)):
            parts = self._dispatch_decode(active)
        with tel.span("device_sync"):
            logits = self._merge_logits(parts, self._sync_rows(
                [dev[:, 0, 0] for dev, _ in parts]))

        logits = self._post_decode_checks(active, logits)

        with tel.span("sample_emit"):
            for i in active:
                lane = self.lanes[i]
                if lane.free:  # guard replay-preempted it after the sync
                    continue
                if (self.chaos is not None and
                        self.chaos.fire("drop_sample", lane=i) is not None):
                    # The sampled token is lost pre-commit; per-tick
                    # landmark-sum updates make an in-place retry unsound,
                    # so recovery is a full replay (recompute preemption).
                    self.sched.preempt(i)
                    continue
                lane.pos += 1
                tel.flight.record(
                    lane.req.uid, "decode", tick=self._tick, pos=lane.pos
                )
                if lane.prompt_left:  # replay prefill: ignore the sample
                    lane.next_token = lane.prompt_left.popleft()
                    continue
                self._emit_token(i, logits[i, : self.cfg.vocab_size])

        if self._frozen_rebase:
            # Lanes whose just-written position starts a new landmark
            # segment: rebase the newly-frozen row exactly and found the
            # new active row over the horizon (skips lanes retired above
            # and lanes demoted to the exact program, which has no drifting
            # active row to rebase).
            hits = [
                i for i in active
                if not self.lanes[i].free
                and self.lanes[i].req.uid not in self._demoted
                and (self.lanes[i].pos - 1) > 0
                and (self.lanes[i].pos - 1) % self._seg == 0
            ]
            if hits:
                with tel.span("rebase", lanes=len(hits)):
                    self._run_rebase(hits)

    # -- continuous-batching tick ----------------------------------------------
    def _tick_chunked(self) -> None:
        """One continuous-batching tick: decode dispatch FIRST (the device
        starts on it immediately), then admissions and a budget's worth of
        prompt chunks dispatched while the decode program runs, then ONE
        host sync at the sample boundary. Decode lanes advance every tick
        no matter how much prefill is pending (the never-starve invariant);
        prefill bandwidth is capped by ``prefill_token_budget`` per tick
        (0 = one chunk), so ITL stays flat under a long-prompt flood."""
        self._begin_tick()
        tel = self.telemetry
        if tel.enabled:
            self._ticks_total.inc()
            fl = tel.flight
            fl.counter_sample("queue_depth", len(self.sched.waiting))
            alloc = self.sched.allocator
            if alloc is not None:
                fl.counter_sample("pool_blocks_used", alloc.num_used)
                fl.counter_sample("pool_fragmentation", alloc.fragmentation())

        # ---- decode dispatch (no sync: chunks below overlap the compute) --
        candidates = [
            i for i, l in enumerate(self.lanes)
            if not l.free and not l.prefilling
            and l.prefilled_tick != self._tick
        ]
        active = []
        for i in candidates:
            if self.lanes[i].free:  # preempted as a victim earlier this loop
                continue
            if not self.sched.ensure_block(i, self.lanes[i].pos):
                continue
            active.append(i)
        active = [i for i in active if not self.lanes[i].free]
        parts: list = []
        if active:
            with tel.span("decode_dispatch", lanes=len(active)):
                parts = self._dispatch_decode(active)

        # ---- admissions: parked requests resume at their chunk boundary --
        with tel.span("admit"):
            admissions = self.sched.admit()
        if admissions:
            self._progress = True
        for i, req in admissions:
            lane = self.lanes[i] = _Lane(req=req)
            parked = self._parked.pop(req.uid, None)
            if parked is not None:
                self.kv.dense_restore(i, parked["snap"])
                lane.prefill_pos = parked["prefill_pos"]
                lane.chunk_idx = parked["chunk_idx"]
                lane.prefilling = True
            elif self._prefix_enabled and self._try_attach_prefix(i, req):
                pass  # lane state set by the attach (full or partial hit)
            else:
                self.kv.zero_lane_dense(i)
                if req.prompt:
                    lane.prefilling = True
                # empty prompt: straight to decode from pos 0, like replay

        # ---- budgeted chunk dispatch (FCFS by admission order) -----------
        budget = self.serve.prefill_token_budget or self._chunk
        max_chunks = max(1, budget // self._chunk)
        prefilling = sorted(
            (i for i, l in enumerate(self.lanes) if not l.free and l.prefilling),
            key=lambda i: self.sched.admit_order.get(
                self.lanes[i].req.uid, 0
            ),
        )
        pending_first: list[tuple[int, object, int]] = []
        launched = 0
        bs = self.serve.block_size
        dispatching = True
        while dispatching:
            dispatching = False
            for i in prefilling:
                if launched >= max_chunks:
                    break
                if self.lanes[i].free:
                    continue  # preempted by a deadlock break this tick
                lane = self.lanes[i]
                req = lane.req
                start = lane.prefill_pos
                cv = min(self._chunk, len(req.prompt) - start)
                if not self.sched.ensure_prefill_blocks(i, start + cv):
                    # pool dry: the chunk stalls, never evicts a decoder
                    continue
                ctoks = np.zeros((1, self._chunk), np.int32)
                ctoks[0, :cv] = req.prompt[start:start + cv]
                from repro.serve.paged import bucket_view_slots

                # the sliced row must span the committed prefix AND the
                # chunk's destination slots (the commit scatter reads its
                # block ids from this row; the wrapper's ZERO_BLOCK padding
                # is overrun guard only, not real slots)
                nbv = bucket_view_slots(
                    start // bs + self._chunk // bs, self.serve.blocks_per_lane
                )
                row = self.sched.table_row(i)[:nbv] if self.kv.paged else None
                with tel.span("prefill_chunk", lane=i, chunk=lane.chunk_idx):
                    lg, new_storage = self._chunk_step(
                        self.kv._storage, row, ctoks, i, start, cv
                    )
                    self.kv._storage = list(new_storage)
                tel.flight.record(
                    req.uid, "prefill_chunk", tick=self._tick,
                    chunk=lane.chunk_idx, tok0=start, tok1=start + cv, lane=i,
                )
                lane.prefill_pos = start + cv
                lane.chunk_idx += 1
                launched += 1
                if lane.prefill_pos >= len(req.prompt):
                    lane.prefilling = False
                    lane.pos = len(req.prompt)
                    lane.prefilled_tick = self._tick
                    pending_first.append((i, lg, cv))
                elif self._prefix_enabled and lane.prefill_pos % bs == 0:
                    # Block-aligned chunk boundary: snapshot the carried
                    # dense state as a partial-hit resume point. The host
                    # copy forces a device sync mid-tick — the documented
                    # cost of building cache entries, paid only while a
                    # prefill runs with the prefix cache on (the final
                    # boundary rides the sample-boundary sync instead).
                    lane.stat_points[lane.prefill_pos] = self.kv.dense_snapshot(i)

            # ---- all-prefill deadlock breaker ----------------------------
            # Every held lane stalled mid-prefill on a dry pool with no
            # decode lane left whose retirement could free blocks: the
            # chunk-stall rule ("a chunk never evicts a decoder") would
            # livelock here, because the stalled prefills hold each other's
            # growth room. Preempt the YOUNGEST stalled prefill and retry
            # dispatch WITHIN this tick, so the FCFS head's
            # ensure_prefill_blocks reclaims the victim's parked blocks
            # before the victim can re-admit (it requeues at the queue
            # front and would otherwise re-take the blocks next tick,
            # thrashing forever). Cascades at most one lane per pass until
            # the head launches. A single stalled lane is left alone: with
            # the whole pool to itself the stall is a sizing error, and
            # self-preemption would thrash instead of progress.
            if not launched:
                stalled = [i for i in prefilling if not self.lanes[i].free]
                decoding = any(
                    not l.free and not l.prefilling for l in self.lanes
                )
                if len(stalled) > 1 and not decoding and not self.sched.parked:
                    self.sched.preempt(stalled[-1])
                    dispatching = True

        if launched:
            self._progress = True

        # ---- ONE sync at the sample boundary -----------------------------
        with tel.span("device_sync"):
            rows = self._sync_rows(
                [dev[:, 0, 0] for dev, _ in parts]
                + [lg[0, cv - 1, : self.cfg.vocab_size]
                   for _, lg, cv in pending_first])
            logits = self._merge_logits(parts, rows[:len(parts)])
            firsts = [(i, row) for (i, _, _), row
                      in zip(pending_first, rows[len(parts):])]

        logits = self._post_decode_checks(active, logits)

        with tel.span("sample_emit"):
            for i in active:
                lane = self.lanes[i]
                if lane.free:  # guard replay-preempted it after the sync
                    continue
                if (self.chaos is not None and
                        self.chaos.fire("drop_sample", lane=i) is not None):
                    self.sched.preempt(i)
                    continue
                lane.pos += 1
                tel.flight.record(
                    lane.req.uid, "decode", tick=self._tick, pos=lane.pos
                )
                self._emit_token(i, logits[i, : self.cfg.vocab_size])
            for i, lg in firsts:
                if self.lanes[i].free:  # cancelled mid-tick
                    continue
                if self._prefix_enabled:
                    # Cache the completed prefill BEFORE emitting (the emit
                    # may retire the lane; the entry's own block references
                    # keep the prefix resident past release).
                    self._maybe_cache_prefix(i, lg)
                self._emit_token(i, lg)

        if self._frozen_rebase:
            hits = [
                i for i in active
                if not self.lanes[i].free
                and self.lanes[i].req.uid not in self._demoted
                and (self.lanes[i].pos - 1) > 0
                and (self.lanes[i].pos - 1) % self._seg == 0
            ]
            if hits:
                with tel.span("rebase", lanes=len(hits)):
                    self._run_rebase(hits)

    def _run_rebase(self, hits: list[int]) -> None:
        """Frozen-mode segment-boundary rebase for the given lanes."""
        positions = np.zeros(self.max_lanes, np.int32)
        flags = np.zeros(self.max_lanes, bool)
        for i in hits:
            positions[i] = self.lanes[i].pos - 1
            flags[i] = True
        pre = (
            {i: self._lane_stream_stats(i) for i in hits}
            if self._drift_mon is not None else None
        )
        tables = self.sched.tables()  # fresh: retirements freed blocks
        nb_view = self.kv.view_blocks_needed(positions, hits)
        self.kv._storage = list(self._rebase_step(
            self.kv._storage, jnp.asarray(tables), jnp.asarray(positions),
            jnp.asarray(flags), nb_view,
        ))
        self._rebases += len(hits)
        self.telemetry.metrics.counter(
            "serve_rebases_total", help="frozen-mode boundary rebases"
        ).inc(len(hits))
        for i in hits:
            self.telemetry.flight.record(
                self.lanes[i].req.uid, "rebase", tick=self._tick,
                pos=int(positions[i]),
            )
        if pre is not None:
            self._probe_rebase_drift(hits, positions, pre)

    def _lane_stream_stats(self, lane: int, acc: bool = True) -> list[tuple]:
        """Host (m, l, acc) triples of one lane's streaming-stat leaves,
        one per attention layer group; (m, l) pairs with ``acc=False``,
        which leaves the largest of the three on the device."""
        s = self.kv._storage
        n = 3 if acc else 2
        return [tuple(np.asarray(s[j][lane]) for j in idx[:n])
                for idx in self._stream_idx]

    def _probe_rebase_drift(self, hits, positions, pre) -> None:
        """The free-residual probe: the rebase just recomputed the boundary
        rows exactly, so streamed(pre) vs exact(post) on those rows IS the
        frozen-mode drift bench_drift measures offline — same formula
        (monitors.bv_row_residual), O(c*d) host math per hit."""
        from repro.telemetry import bv_row_residual

        for i in hits:
            p = int(positions[i])
            j = p // self._seg  # new active row; j-1 just froze
            rows = [j - 1, j] if j > 0 else [j]
            post = self._lane_stream_stats(i)
            res = max(
                bv_row_residual((pl, pa), (ql, qa), rows)
                for (_, pl, pa), (_, ql, qa) in zip(pre[i], post)
            )
            self._drift_mon.observe(res)
            if self._spectrum_mon is not None:
                m = np.stack([g[0] for g in post])
                l = np.stack([g[1] for g in post])
                self._spectrum_mon.observe(
                    m, l, min(p // self._seg + 1, self.cfg.num_landmarks)
                )

    # -- maintenance -----------------------------------------------------------
    def defragment(self) -> int:
        """Compact live blocks onto the lowest pool ids (e.g. before
        shrinking or snapshotting the pool) and permute device storage to
        match. Safe between ticks; block tables stay valid. Returns the
        number of blocks moved."""
        if self.sched.allocator is None:
            return 0
        mapping = self.sched.allocator.defragment()
        self.kv.apply_mapping(mapping)
        return len(mapping)

    # -- introspection ---------------------------------------------------------
    def stats(self) -> dict:
        st = self.sched.stats()
        st["mode"] = (
            f"{'paged' if self.kv.has_paged_leaves else 'dense'}"
            f"+{'chunked' if self._chunked else 'batched' if self.batched else 'replay'}-prefill"
        )
        bt = self.decode_plan.block_table
        st["decode_plan"] = (
            f"{self.decode_plan.impl}/b{self.decode_plan.block_n}"
            + (f"/t{bt}" if bt else "")
            + f"/{self.decode_plan.source}"
        )
        st["decode_streaming"] = self.cfg.decode_streaming
        st["decode_impl"] = self.decode_impl
        if self._frozen_rebase:
            st["rebases"] = self._rebases
        st["quarantines"] = int(self._quarantines.value)
        st["demotions"] = int(self._demotions.value)
        st["watchdog_fires"] = int(self._wd_fires.value)
        if self.chaos is not None:
            st["chaos_injections"] = self.chaos.injections
        if self.prefix is not None:
            st["prefix"] = self.prefix.stats()
        if self.telemetry.enabled:
            st["telemetry"] = self.telemetry.tracer.summary()
            st["flight"] = self.telemetry.flight.summary()
            if self._acct is not None:
                st["xla_compiles"] = {
                    p: self._acct.compiles(p)
                    for p in ("prefill", "prefill_chunk", "decode_tick",
                              "rebase", "prefix_attach", "decode_exact")
                }
        return st
