"""Block-paged KV cache: vLLM-style fixed-size token blocks over the
spectral-shift decode state.

Two pieces:

* ``BlockAllocator`` — host-side bookkeeping: a free list of fixed-size
  token blocks, per-request block tables, alloc/free/defragment and
  utilization stats. Block 0 is reserved as the permanently-zero block that
  backs unallocated block-table slots, so gathers never need a validity
  mask (the decode path's causal key mask already ignores positions past
  ``pos``).

* ``PagedKVCache`` — maps the ``cache_specs`` ParamSpec tree onto
  block-shaped device storage. Leaves with a ``cache_seq`` axis (attention
  K/V, MLA latents) live in shared block pools: the ``cache_seq`` axis of
  each spec is replaced *in place* by a ``(num_blocks, block_size)`` pair,
  so a stacked-layer leaf ``(L, B, H, S, D)`` pools as
  ``(L, B, H, num_blocks, block_size, D)`` — the layer axis stays leading
  and the tree remains ``lax.scan``-compatible without any per-tick
  transpose. ``D`` is stored rounded up to whole 128-lane tiles
  (``kernels.paged_decode.lane_width``, zero columns); reads cut it
  back. Everything else (landmark running sums, streaming B-side stats,
  SSM states, ``pos``) is small and fixed-size, so it stays dense per
  lane exactly like the seed engine. ``write_prefill`` installs a
  batched prefill's result; ``gather_views`` assembles the lane-stacked
  dense tree for inspection/tests.

The memory win is at the pool: ``num_blocks`` is sized to the expected
working set, not ``max_lanes * max_seq``. Two decode-tick programs exist:

* ``make_fused_step`` — the legacy *gather* route: assemble transient
  dense per-lane views (O(S) HBM traffic per tick), run the batched decode
  step, scatter the touched block back. Kept as the ``recompute``-mode
  baseline; the frozen-mode boundary rebase (``make_rebase_step``) also
  reads through this gather.
* ``make_paged_step`` — the *gather-free* route
  (``ServeConfig.decode_impl="paged"``): the decode step reads K/V
  directly from the shared pools through the block-table-aware Pallas
  kernel (``kernels/paged_decode.py`` — the lane's block table rides into
  the kernel as a scalar-prefetch SMEM operand that addresses the DMA of
  each pool block, so no dense view ever exists), and the new token's K/V
  commits via a single-block scatter. A ``decode_streaming="frozen"``
  tick therefore touches only the written block plus the dense stats
  leaves: O(c*d) + one block per token, independent of the horizon.

Each program is named after what it does, so a device trace tells them
apart (``jit(decode_gather)``, ``jit(decode_paged)``,
``jit(prefill_chunk)``, ``jit(rebase)``).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.kernels.paged_decode import lane_width
from repro.models.params import ParamSpec
from repro.serve.kv_cache import cache_leaf_layout

ZERO_BLOCK = 0  # reserved all-zero block id backing unallocated table slots


def bucket_view_slots(need: int, cap: int, quantum: int = 0) -> int:
    """Round a required block-table slot count up to a compile bucket:
    next power of two by default, or the next multiple of ``quantum``
    (a measured ``Plan.block_table``), capped at ``cap``. One compiled
    tick program exists per distinct result — shared by the engine's
    ``view_blocks_needed`` and the decode autotune harness so the sweep
    times exactly the grid shapes the engine runs."""
    if quantum > 0:
        return min(-(-need // quantum) * quantum, cap)
    nb = 1
    while nb < need:
        nb *= 2
    return min(nb, cap)


# ==========================================================================
# Host-side block bookkeeping
# ==========================================================================
class BlockAllocator:
    """Free-list allocator of fixed-size token blocks with per-request
    block tables. Pure host-side bookkeeping; device storage is owned by
    ``PagedKVCache``."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least one allocatable block past block 0")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # LIFO free list (recently freed blocks are reused first — they are
        # the ones most likely still resident in cache). Block 0 excluded.
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self.tables: dict[int, list[int]] = {}  # request uid -> block ids
        # Reference count per non-free block: one per block table holding
        # it plus one per PrefixCache entry retaining it. Invariant: every
        # id in 1..num_blocks-1 is either on the free list (absent here) or
        # present with count >= 1 — a block re-enters the free list only
        # when its count drops to zero, never while still referenced.
        self.refcounts: dict[int, int] = {}
        # Optional PrefixCache hook: when an allocation comes up short, LRU
        # cached prefixes whose blocks are otherwise unreferenced are
        # evicted to make room before the allocation fails.
        self.prefix_cache: Optional["PrefixCache"] = None
        # Optional ChaosInjector (serve/chaos.py): "alloc_fail" makes
        # _take_free report a shortfall even when blocks are free.
        self.chaos = None

    # -- queries ------------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def can_alloc(self, n_blocks: int) -> bool:
        avail = self.num_free
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable_blocks()
        return n_blocks <= avail

    def refcount(self, block: int) -> int:
        return self.refcounts.get(block, 0)

    def fragmentation(self) -> float:
        """Free-list fragmentation in [0, 1]: 1 minus the longest
        contiguous run of free block ids over the free count. 0 when the
        free space is one contiguous range (or empty) — the regime where
        ``defragment()`` has nothing to do."""
        if not self._free:
            return 0.0
        ids = sorted(self._free)
        best = run = 1
        for a, b in zip(ids, ids[1:]):
            run = run + 1 if b == a + 1 else 1
            best = max(best, run)
        return 1.0 - best / len(ids)

    def stats(self) -> dict:
        usable = self.num_blocks - 1
        return {
            "num_blocks": usable,
            "blocks_used": self.num_used,
            "blocks_free": self.num_free,
            "blocks_shared": sum(
                1 for rc in self.refcounts.values() if rc > 1
            ),
            "utilization": self.num_used / max(usable, 1),
            "fragmentation": self.fragmentation(),
            "requests": len(self.tables),
        }

    # -- mutation -----------------------------------------------------------
    def _take_free(self, n_blocks: int) -> Optional[list[int]]:
        """Pop ``n_blocks`` off the free list at refcount 1, LRU-evicting
        reclaimable prefix-cache entries to cover a shortfall. Returns None
        (no state change beyond evictions) if still short."""
        if self.chaos is not None and self.chaos.fire("alloc_fail"):
            return None
        while n_blocks > self.num_free:
            if self.prefix_cache is None or not self.prefix_cache.evict_one(
                reclaim_only=True
            ):
                return None
        got = [self._free.pop() for _ in range(n_blocks)]
        for b in got:
            self.refcounts[b] = 1
        return got

    def alloc(self, uid: int, n_blocks: int) -> Optional[list[int]]:
        """Append ``n_blocks`` fresh blocks to ``uid``'s table. Returns the
        new block ids, or None (no state change) if the pool is short."""
        got = self._take_free(n_blocks)
        if got is None:
            return None
        self.tables.setdefault(uid, []).extend(got)
        return got

    def free(self, uid: int) -> list[int]:
        """Drop ``uid``'s reference on every block in its table. Blocks
        whose refcount hits zero go back to the free list; blocks still
        retained elsewhere (a cached prefix, another table) stay resident.
        Returns the ids actually freed."""
        blocks = self.tables.pop(uid, [])
        freed = []
        for b in reversed(blocks):
            rc = self.refcounts[b] - 1
            if rc:
                self.refcounts[b] = rc
            else:
                del self.refcounts[b]
                self._free.append(b)
                freed.append(b)
        return freed

    def take_ref(self, block: int) -> None:
        """Add a reference to an already-resident block (PrefixCache
        retention, shared-prefix attach). Never valid on a free block."""
        if block not in self.refcounts:
            raise ValueError(f"take_ref on free block {block}")
        self.refcounts[block] += 1

    def release_ref(self, block: int) -> bool:
        """Drop one reference; returns True if the block was freed."""
        rc = self.refcounts[block] - 1
        if rc:
            self.refcounts[block] = rc
            return False
        del self.refcounts[block]
        self._free.append(block)
        return True

    def attach_shared(self, uid: int, blocks: list[int]) -> None:
        """Map already-resident blocks (a matched cached prefix) into the
        FRONT of ``uid``'s table, taking a reference on each — the prefix
        occupies table positions 0..len(blocks)-1 and is released through
        the normal ``free(uid)`` path. The blocks are charged against the
        budget exactly once pool-wide: admission only allocates the tail."""
        for b in blocks:
            self.take_ref(b)
        self.tables.setdefault(uid, [])[:0] = list(blocks)

    def cow(self, uid: int, slot: int) -> Optional[tuple[int, int]]:
        """Copy-on-write: break the sharing of ``uid``'s table ``slot``.
        Allocates a fresh block, points the table at it and drops one
        reference on the shared original (which stays resident for its
        other holders). Returns ``(old, new)`` so the caller can copy the
        device rows (``PagedKVCache.copy_block``), or None if the pool is
        short (caller falls back to its reclaim/preempt loop)."""
        old = self.tables[uid][slot]
        got = self._take_free(1)
        if got is None:
            return None
        new = got[0]
        self.tables[uid][slot] = new
        self.refcounts[old] -= 1  # > 1 before the call, so never frees
        return old, new

    def scramble_free(self, key: int) -> None:
        """Deterministically shuffle the free list (chaos "fragment" site):
        destroys the LIFO locality so subsequent allocations land on
        scattered block ids — the regime ``defragment()`` exists for.
        Pure reordering; allocator accounting is untouched."""
        rng = np.random.default_rng(key if key >= 0 else -key)
        perm = rng.permutation(len(self._free))
        self._free = [self._free[i] for i in perm]

    def defragment(self) -> dict[int, int]:
        """Compact movable live blocks onto the lowest ids. Blocks with
        refcount > 1 (shared between tables and/or a cached prefix) are
        PINNED in place — moving one would have to rewrite every holder's
        view mid-flight, so the compactor refuses and packs around them.
        Returns the {old: new} mapping (identity entries omitted); the
        caller must permute device storage with the same mapping
        (``PagedKVCache.apply_mapping``). Singly-referenced prefix-cache
        blocks DO move; their index entries are remapped here."""
        pinned = {b for b, rc in self.refcounts.items() if rc > 1}
        movable = sorted(b for b, rc in self.refcounts.items() if rc == 1)
        targets, cand = [], 1
        while len(targets) < len(movable):
            if cand not in pinned:
                targets.append(cand)
            cand += 1
        mapping = {
            old: new for old, new in zip(movable, targets) if old != new
        }
        if mapping:
            for blocks in self.tables.values():
                blocks[:] = [mapping.get(b, b) for b in blocks]
            self.refcounts = {
                mapping.get(b, b): rc for b, rc in self.refcounts.items()
            }
            if self.prefix_cache is not None:
                self.prefix_cache.remap(mapping)
            occupied = set(self.refcounts)
            self._free = [
                b for b in range(self.num_blocks - 1, 0, -1)
                if b not in occupied
            ]
        return mapping


# ==========================================================================
# Content-hash prefix index
# ==========================================================================
@dataclasses.dataclass
class PrefixEntry:
    """One cached prompt. ``blocks`` are the physical pool blocks covering
    ``n_tokens`` (``ceil(n_tokens / block_size)`` of them — the last one may
    be partial, shared via copy-on-write). ``stat_points`` maps block-aligned
    token boundaries to ``PagedKVCache.dense_snapshot`` host copies of the
    lane-dense landmark/streaming state captured at that boundary under the
    canonical (engine-horizon) segmentation; ``logits`` is the next-token
    logits row after the full prompt, enabling a zero-compute full hit."""

    blocks: list[int]
    n_tokens: int
    tail: list[int]             # prompt tokens past the last full block
    hashes: list[bytes]         # chained digest after each full block
    stat_points: dict[int, list]
    logits: Optional[np.ndarray]
    last_used: int = 0
    pins: int = 0  # in-flight admissions between probe and attach


class PrefixCache:
    """Content-hash index of cached prompt prefixes over the block pool.

    Hash scheme — chained, block-granular: digest ``i`` is
    ``sha1(digest[i-1] || int32-LE tokens of block i)`` with
    ``digest[-1] = b""``. Chaining makes digest ``i`` a fingerprint of
    tokens ``[0, (i+1)*block_size)``, so matching a prompt is one dict
    lookup per block boundary, longest first — no trie needed. Only full
    blocks are hashed; a ragged prompt tail is compared verbatim (an
    exact-full-prompt hit additionally shares the partial last block, which
    divergent decode writes then copy-on-write).

    The index holds one key per block boundary of each entry, first-wins on
    collision (an existing key's backing blocks stay authoritative; a later
    identical prefix simply isn't re-cached). Entries may OVERLAP: a
    partial-hit completion inserts a longer entry whose leading blocks are
    an earlier entry's — each entry takes its own allocator reference per
    block, tracked here in ``_cache_refs`` so eviction can tell cache-held
    references apart from live block tables. Eviction is LRU by last use;
    ``reclaim_only`` eviction considers entries no live table references
    (allocator refcount fully accounted for by cache entries), evicting
    overlapping chains in cascade — any single eviction may free nothing
    (its blocks still held by a longer entry), but each removes an entry,
    so the allocator's shortfall loop keeps making progress until the
    chain's blocks actually reach the free list. Entry blocks carry one
    allocator reference per holding entry, so a shared prefix never
    re-enters the free list while a live request still maps it — the
    allocator invariant the defragmenter and ``reclaim_parked`` rely
    on."""

    def __init__(self, allocator: BlockAllocator, max_blocks: int = 0,
                 registry=None):
        from repro.telemetry.metrics import MetricsRegistry, TICK_BUCKETS

        self.allocator = allocator
        self.block_size = allocator.block_size
        self.max_blocks = max_blocks
        self._index: dict[bytes, tuple[PrefixEntry, int]] = {}
        self._entries: list[PrefixEntry] = []
        # block id -> number of cache entries holding a reference on it
        # (overlapping entries share blocks; see the class docstring)
        self._cache_refs: dict[int, int] = {}
        self._clock = 0
        self.registry = registry if registry is not None else MetricsRegistry()
        r = self.registry
        self._hits = r.counter(
            "prefix_cache_hits_total",
            help="admissions attached to a cached prefix")
        self._misses = r.counter(
            "prefix_cache_misses_total",
            help="admissions that found no usable cached prefix")
        self._evictions = r.counter(
            "prefix_cache_evictions_total",
            help="cached prefixes dropped (LRU cap or pool pressure)")
        self._hit_blocks = r.histogram(
            "prefix_hit_blocks", help="shared blocks mapped per cache hit",
            buckets=TICK_BUCKETS)
        # Optional ChaosInjector: "hash_collision" perturbs lookup digests
        # so a warm prompt cold-misses (see match()).
        self.chaos = None
        allocator.prefix_cache = self

    # -- hashing -------------------------------------------------------------
    @staticmethod
    def block_hashes(prompt, block_size: int) -> list[bytes]:
        """Chained digest after each FULL block of ``prompt``."""
        out: list[bytes] = []
        d = b""
        for i in range(len(prompt) // block_size):
            blk = np.asarray(
                prompt[i * block_size:(i + 1) * block_size], np.int32
            ).tobytes()
            d = hashlib.sha1(d + blk).digest()
            out.append(d)
        return out

    # -- lookup --------------------------------------------------------------
    def match(self, prompt) -> Optional[tuple[PrefixEntry, int]]:
        """Longest cached prefix of ``prompt``: ``(entry, k)`` with ``k``
        matched full blocks, or None. Pure lookup — the caller decides
        whether the match is usable and accounts hit/miss accordingly."""
        hashes = self.block_hashes(prompt, self.block_size)
        if self.chaos is not None and self.chaos.fire("hash_collision"):
            # An injected "collision" perturbs the lookup digests so the
            # probe cold-misses. (Delivering WRONG blocks — a true
            # collision — would be undetectable by construction; the
            # injectable failure mode is the conservative one: lost reuse,
            # never lost correctness.)
            hashes = [hashlib.sha1(b"chaos" + d).digest() for d in hashes]
        for i in range(len(hashes) - 1, -1, -1):
            got = self._index.get(hashes[i])
            if got is not None and got[1] >= i + 1:
                return got[0], i + 1
        return None

    def is_full_hit(self, entry: PrefixEntry, prompt, k: int) -> bool:
        """True when ``(entry, k)`` covers ``prompt`` exactly: every full
        block matched, the ragged tails agree verbatim, and the entry
        carries the post-prompt logits row for the zero-compute emit."""
        bs = self.block_size
        return (
            k == len(prompt) // bs
            and entry.n_tokens == len(prompt)
            and entry.tail == list(prompt[k * bs:])
            and entry.logits is not None
        )

    def note_hit(self, entry: PrefixEntry, n_blocks: int) -> None:
        self._clock += 1
        entry.last_used = self._clock
        self._hits.inc()
        self._hit_blocks.observe(n_blocks)

    def note_miss(self) -> None:
        self._misses.inc()

    def pin(self, entry: PrefixEntry) -> None:
        """Soft-pin an entry across an admission window (probe -> attach),
        bumping its LRU stamp: pinned entries are the LAST reclaim
        candidates rather than excluded outright — a hard pin could
        deadlock admission when the pinned entry's own blocks are the only
        reclaimable room left, whereas evicting it merely downgrades the
        accounted hit to a cold miss (which the attach path re-detects)."""
        self.touch(entry)
        entry.pins += 1

    def unpin(self, entry: PrefixEntry) -> None:
        entry.pins = max(entry.pins - 1, 0)

    def touch(self, entry: PrefixEntry) -> None:
        """LRU-bump without pinning (re-probe of an already-pinned entry)."""
        self._clock += 1
        entry.last_used = self._clock

    # -- insertion / eviction -------------------------------------------------
    def insert(self, prompt, blocks, stat_points=None,
               logits=None) -> Optional[PrefixEntry]:
        """Cache a finished prefill: take a reference on the blocks covering
        the prompt and register the boundary digests. Returns the entry, or
        None when nothing was cached (sub-block prompt, or every boundary
        already indexed by an earlier entry — first wins)."""
        bs = self.block_size
        hashes = self.block_hashes(prompt, bs)
        if not hashes:
            return None
        nb = -(-len(prompt) // bs)
        blocks = list(blocks[:nb])
        if len(blocks) < nb:
            return None
        self._clock += 1
        entry = PrefixEntry(
            blocks=blocks, n_tokens=len(prompt),
            tail=list(prompt[len(hashes) * bs:]), hashes=hashes,
            stat_points=dict(stat_points or {}),
            logits=None if logits is None else np.asarray(logits),
            last_used=self._clock,
        )
        registered = False
        for i, d in enumerate(hashes):
            if d not in self._index:
                self._index[d] = (entry, i + 1)
                registered = True
        if not registered:
            return None
        for b in blocks:
            self.allocator.take_ref(b)
            self._cache_refs[b] = self._cache_refs.get(b, 0) + 1
        self._entries.append(entry)
        while (
            self.max_blocks > 0 and self.block_count() > self.max_blocks
            and self.evict_one()
        ):
            pass
        return entry

    def _reclaimable(self, entry: PrefixEntry) -> bool:
        """No live block table references any of the entry's blocks: the
        allocator refcount is fully accounted for by cache entries. Such
        entries are safe eviction fodder even when overlapping entries
        keep some blocks resident — the sweep cascades down the chain."""
        return all(
            self.allocator.refcount(b) == self._cache_refs.get(b, 0)
            for b in entry.blocks
        )

    def evictable_blocks(self) -> int:
        """Distinct blocks a full reclaim-only eviction sweep would return
        to the free list right now: blocks of cache-only entries, minus
        any also held by an entry some live table still references (those
        survive the sweep). Exact — ``can_alloc`` promises on it."""
        freeable: set[int] = set()
        held: set[int] = set()
        for e in self._entries:
            (freeable if self._reclaimable(e) else held).update(e.blocks)
        return len(freeable - held)

    def evict_one(self, reclaim_only: bool = False) -> bool:
        """Drop the LRU entry. ``reclaim_only`` restricts candidates to
        entries no live table references (allocator shortfall path):
        evicting those in LRU order cascades overlapping prefix chains —
        one eviction may free nothing (its blocks still held by a longer
        entry), but each removes an entry, so the shortfall loop either
        reaches the free list or runs out of candidates. Soft-pinned
        entries (an admission in flight between probe and attach) are
        taken only when no unpinned candidate remains."""
        cands = [
            e for e in self._entries
            if not reclaim_only or self._reclaimable(e)
        ]
        if not cands:
            return False
        unpinned = [e for e in cands if not e.pins]
        victim = min(unpinned or cands, key=lambda e: e.last_used)
        for d in victim.hashes:
            got = self._index.get(d)
            if got is not None and got[0] is victim:
                del self._index[d]
        self._entries.remove(victim)
        for b in victim.blocks:
            rc = self._cache_refs[b] - 1
            if rc:
                self._cache_refs[b] = rc
            else:
                del self._cache_refs[b]
            self.allocator.release_ref(b)
        self._evictions.inc()
        return True

    def remap(self, mapping: dict[int, int]) -> None:
        """Follow a defragmentation: entry block ids move with the pool.
        (Digests are content-addressed and don't change.)"""
        for e in self._entries:
            e.blocks = [mapping.get(b, b) for b in e.blocks]
        self._cache_refs = {
            mapping.get(b, b): rc for b, rc in self._cache_refs.items()
        }

    def block_count(self) -> int:
        return sum(len(e.blocks) for e in self._entries)

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "blocks": self.block_count(),
            "index_keys": len(self._index),
            "hits": int(self._hits.value),
            "misses": int(self._misses.value),
            "evictions": int(self._evictions.value),
        }


# ==========================================================================
# Device-side block-pool storage
# ==========================================================================
@dataclasses.dataclass
class _LeafInfo:
    spec: ParamSpec
    seq_axis: Optional[int]  # index of the cache_seq axis, None = dense leaf


def _leaf_infos(cfg: ModelConfig, max_seq: int) -> tuple[list[_LeafInfo], Any]:
    leaves, treedef = cache_leaf_layout(cfg, max_seq)
    return [_LeafInfo(spec, j) for spec, j in leaves], treedef


def fit_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` with its last (feature) axis zero-padded or cut to ``width``:
    between a pool's stored width and the model's."""
    if x.shape[-1] > width:
        return x[..., :width]
    if x.shape[-1] < width:
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                       + [(0, width - x.shape[-1])])
    return x


class PagedKVCache:
    """Block-pool device storage for one engine's decode state.

    With ``paged=False`` every leaf (including K/V) is stored lane-dense —
    bitwise the seed engine's layout — which is the comparison baseline for
    the paged path and the fallback when a model has no sequence-shaped
    cache at all (pure SSM stacks)."""

    def __init__(self, cfg: ModelConfig, serve: ServeConfig):
        self.cfg, self.serve = cfg, serve
        self.block_size = serve.block_size
        self.max_lanes, self.max_seq = serve.max_lanes, serve.max_seq
        self.num_blocks = serve.resolved_num_blocks
        self.infos, self.treedef = _leaf_infos(cfg, serve.max_seq)
        self.paged = serve.paged and any(
            i.seq_axis is not None for i in self.infos
        )
        self._storage: list[jnp.ndarray] = []
        for info in self.infos:
            dt = info.spec.dtype or jnp.float32
            if self.paged and info.seq_axis is not None:
                # Pool layout: the cache_seq axis splits IN PLACE into
                # (num_blocks, block_size), so leading layer/batch axes stay
                # leading (lax.scan over layers keeps working on pools).
                # The feature axis is stored lane-padded (zero columns;
                # MLA's 64-wide rope keys take 128): the paged decode
                # kernel's DMAs move whole 128-lane tiles, and TPU HBM
                # holds a narrower axis padded to the tile anyway.
                j = info.seq_axis
                shape = info.spec.shape
                self._storage.append(jnp.zeros(
                    (*shape[:j], self.num_blocks, self.block_size,
                     *shape[j + 1:-1], lane_width(shape[-1])), dt,
                ))
            else:
                self._storage.append(
                    jnp.zeros((self.max_lanes, *info.spec.shape), dt)
                )

    @property
    def has_paged_leaves(self) -> bool:
        return self.paged

    def pool_tokens(self) -> int:
        """Capacity of the shared pool, in tokens (0 when not paged)."""
        return (self.num_blocks - 1) * self.block_size if self.paged else 0

    # -- assemble the dense view decode_step expects -------------------------
    def _gather_leaf(self, arr, info: _LeafInfo, tables) -> jnp.ndarray:
        """Pool (..., num_blocks, bs, ...) + tables (rows, nb) ->
        row-stacked view (rows, ..., nb*bs, ...). ``rows`` is usually
        ``max_lanes`` (decode tick) but can be 1 (a single lane's view for
        a chunked-prefill step)."""
        j = info.seq_axis
        shape = info.spec.shape
        # take with 2D indices at the block axis: (..., rows, nb, bs, ...)
        g = jnp.take(arr, tables, axis=j)
        g = jnp.moveaxis(g, j, 0)          # rows leading
        view_len = tables.shape[1] * self.block_size
        g = g.reshape(tables.shape[0], *shape[:j], view_len,
                      *arr.shape[j + 2:])
        return fit_lanes(g, shape[-1])

    def gather_views(self, tables: np.ndarray) -> Any:
        """tables (max_lanes, blocks_per_lane) int32, ZERO_BLOCK where
        unallocated. Returns the lane-stacked dense cache tree: every leaf
        (max_lanes, *spec.shape)."""
        tb = jnp.asarray(tables, jnp.int32)
        leaves = [
            arr if (not self.paged or info.seq_axis is None)
            else self._gather_leaf(arr, info, tb)
            for arr, info in zip(self._storage, self.infos)
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- write paths ----------------------------------------------------------
    def write_prefill(
        self, lane: int, prefill_tree: Any, table_row: np.ndarray,
        n_tokens: int,
    ) -> None:
        """Install a batched-prefill result (a B=1 cache tree whose seq
        leaves are padded-prompt long, a block multiple) into ``lane``:
        the first ``ceil(n_tokens / block_size)`` blocks of each seq leaf go
        to the lane's allocated blocks (positions past ``n_tokens`` are
        zero-masked, matching what unallocated slots read as), dense leaves
        overwrite the lane's dense slots."""
        new_leaves = jax.tree_util.tree_leaves(prefill_tree)
        bs = self.block_size
        nb = -(-n_tokens // bs)
        for idx, info in enumerate(self.infos):
            j = info.seq_axis
            leaf = new_leaves[idx]
            if not self.paged or j is None:
                if j is not None and leaf.shape[j] != self.max_seq:
                    pad = [(0, 0)] * leaf.ndim
                    pad[j] = (0, self.max_seq - leaf.shape[j])
                    leaf = jnp.pad(leaf, pad)
                self._storage[idx] = self._storage[idx].at[lane].set(leaf)
                continue
            if leaf.shape[j] % bs:  # ss_fused runs unpadded prompt lengths
                pad = [(0, 0)] * leaf.ndim
                pad[j] = (0, -leaf.shape[j] % bs)
                leaf = jnp.pad(leaf, pad)
            shape = leaf.shape
            n_blocks_pad = shape[j] // bs
            split = leaf.reshape(
                *shape[:j], n_blocks_pad, bs, *shape[j + 1:]
            )
            pre = (slice(None),) * j
            ids = jnp.asarray(table_row[:nb], jnp.int32)
            arr = self._storage[idx]
            self._storage[idx] = arr.at[(*pre, ids)].set(
                fit_lanes(split[(*pre, slice(0, nb))], arr.shape[-1])
            )

    def make_fused_step(self, decode_step_fn, params):
        """One jitted XLA program for the whole decode tick:
        gather lane views from the pool -> batched decode step -> commit
        (dense leaves masked to active lanes; the touched K/V block of each
        active lane scattered back). Pool buffers are donated, so block
        writes update in place instead of copying the pool every tick.

        Views are gathered only ``n_view_blocks`` long — the engine passes
        the (bucketed) block count of the longest active sequence, so short
        working sets pay short gathers and short attention reads; the
        decode step's ``seq_max`` keeps landmark segmentation pinned to the
        full horizon regardless of view length.

        ``decode_step_fn(params, cache, tokens) -> (logits, new_cache)`` is
        the per-lane step, vmapped here over lanes with ``params`` shared.
        ``params`` enter the program as an argument, never as captured
        constants (XLA would embed every weight in the program).

        Returns ``fn(storage, tables, tokens, positions, active,
        n_view_blocks) -> (logits, new_storage)``; one XLA program compiles
        per distinct ``n_view_blocks``; the engine swaps its storage list
        for the returned one."""
        infos, treedef = self.infos, self.treedef
        paged, bs = self.paged, self.block_size
        n_lanes = self.max_lanes
        vstep = jax.vmap(decode_step_fn, in_axes=(None, 0, 0))

        def decode_gather(storage, params, tables, tokens, positions, active):
            views = [
                arr if (not paged or info.seq_axis is None)
                else self._gather_leaf(arr, info, tables)
                for arr, info in zip(storage, infos)
            ]
            cache = jax.tree_util.tree_unflatten(treedef, views)
            logits, new_cache = vstep(params, cache, tokens)
            new_leaves = jax.tree_util.tree_leaves(new_cache)
            out = []
            for arr, new, info in zip(storage, new_leaves, infos):
                if not paged or info.seq_axis is None:
                    mask = active.reshape((n_lanes,) + (1,) * (arr.ndim - 1))
                    out.append(jnp.where(mask, new.astype(arr.dtype), arr))
                    continue
                j = info.seq_axis

                def ext(per_lane, p, j=j):
                    return jax.lax.dynamic_slice_in_dim(
                        per_lane, (p // bs) * bs, bs, axis=j
                    )

                blocks = jax.vmap(ext)(new, positions)
                ids = tables[jnp.arange(n_lanes), positions // bs]
                # inactive lanes dump into the zero block, re-zeroed below
                ids = jnp.where(active, ids, ZERO_BLOCK)
                pre = (slice(None),) * j
                pool = arr.at[(*pre, ids)].set(fit_lanes(
                    jnp.moveaxis(blocks, 0, j).astype(arr.dtype),
                    arr.shape[-1],
                ))
                pool = pool.at[(*pre, ZERO_BLOCK)].set(
                    jnp.zeros_like(pool[(*pre, ZERO_BLOCK)])
                )
                out.append(pool)
            return logits, out

        jitted = jax.jit(decode_gather, donate_argnums=(0,))

        def call(storage, tables, tokens, positions, active, n_view_blocks):
            if self.paged:
                tables = tables[:, :n_view_blocks]
            return jitted(storage, params, tables, tokens, positions, active)

        call._jitted = jitted  # jit-cache probe for telemetry/accounting.py
        return call

    def make_paged_step(self, decode_step_fn, params):
        """One jitted XLA program for the *gather-free* decode tick
        (``ServeConfig.decode_impl="paged"``): pool leaves are broadcast
        unbatched through the lane vmap, the per-lane block table rides
        along as a traced operand (reaching the Pallas decode kernel in
        ``kernels/paged_decode.py`` as a scalar-prefetch SMEM input that
        addresses the DMA of each pool block), and every seq-shaped cache
        leaf comes back from the step as the lane's NEW TOKEN only —
        committed here with a single-block scatter. No dense view of the
        horizon is ever materialized: a ``decode_streaming="frozen"`` tick
        touches the dense stats leaves plus exactly one pool block per
        lane.

        ``decode_step_fn(params, cache, tokens, table) -> (logits,
        new_cache)`` must be the paged-mode decode step (``serve/decode.py``
        with ``paged_meta`` set): it never writes pool leaves and returns
        seq leaves with a length-1 seq axis holding the new token.
        ``params`` enter the program as an argument, as in
        ``make_fused_step``.

        Returns ``fn(storage, tables, tokens, positions, active,
        n_view_blocks) -> (logits, new_storage)``; like ``make_fused_step``
        one XLA program compiles per distinct (bucketed) ``n_view_blocks``
        and pool buffers are donated, so block writes update in place."""
        if not self.paged:
            raise ValueError(
                "make_paged_step needs paged seq leaves; use make_fused_step"
            )
        infos, treedef = self.infos, self.treedef
        bs = self.block_size
        n_lanes = self.max_lanes

        cache_axes = jax.tree_util.tree_unflatten(
            treedef, [None if i.seq_axis is not None else 0 for i in infos]
        )
        vstep = jax.vmap(decode_step_fn, in_axes=(None, cache_axes, 0, 0))

        def decode_paged(storage, params, tables, tokens, positions, active):
            cache = jax.tree_util.tree_unflatten(treedef, storage)
            logits, new_cache = vstep(params, cache, tokens, tables)
            new_leaves = jax.tree_util.tree_leaves(new_cache)
            ids = tables[jnp.arange(n_lanes), positions // bs]
            # inactive lanes dump into the zero block, re-zeroed below
            ids = jnp.where(active, ids, ZERO_BLOCK)
            offs = positions % bs
            out = []
            for arr, new, info in zip(storage, new_leaves, infos):
                j = info.seq_axis
                if j is None:
                    mask = active.reshape((n_lanes,) + (1,) * (arr.ndim - 1))
                    out.append(jnp.where(mask, new.astype(arr.dtype), arr))
                    continue
                # new (lanes, *shape[:j], 1, *shape[j+1:]): the new token.
                # Adjacent advanced indices (ids, offs) land at the pool's
                # (block, in-block) axes, so the scatter touches one token
                # row per leaf per lane.
                pre = (slice(None),) * j
                vals = jnp.moveaxis(jnp.squeeze(new, axis=1 + j), 0, j)
                pool = arr.at[(*pre, ids, offs)].set(
                    fit_lanes(vals.astype(arr.dtype), arr.shape[-1]))
                pool = pool.at[(*pre, ZERO_BLOCK)].set(
                    jnp.zeros_like(pool[(*pre, ZERO_BLOCK)])
                )
                out.append(pool)
            return logits, out

        jitted = jax.jit(decode_paged, donate_argnums=(0,))

        def call(storage, tables, tokens, positions, active, n_view_blocks):
            return jitted(storage, params, tables[:, :n_view_blocks], tokens,
                          positions, active)

        call._jitted = jitted  # jit-cache probe for telemetry/accounting.py
        return call

    def make_chunk_step(self, chunk_fn, chunk_pad: int, params):
        """One jitted XLA program for a chunked-prefill step of ONE lane:
        gather the lane's committed-prefix view from the pool (plus its
        carried dense landmark/streaming leaves) -> run ``chunk_fn`` (a
        ``make_chunk_prefill_fn`` function of ``(params, cache, tokens,
        start, chunk_valid)``: one fixed-size prompt chunk at global
        positions start..start+chunk_valid-1) -> commit the chunk's
        K/V into the lane's blocks and the carried-forward dense state into
        the lane's dense slots. Pool buffers are donated, so the commit
        updates in place — a chunk step touches ``chunk_pad / block_size``
        blocks plus the lane's dense leaves, independent of the horizon.

        ``chunk_pad`` must be a ``block_size`` multiple and chunk starts
        must be block-aligned (the engine rounds the chunk size up); the
        final ragged chunk rides with ``chunk_valid < chunk_pad`` and its
        partial block commits zero-masked, exactly like ``write_prefill``.

        Returns ``fn(storage, table_row, tokens, lane, start, chunk_valid)
        -> (logits, new_storage)`` with ``table_row`` the lane's block table
        sliced to the engine's bucketed view length (ignored when the cache
        is lane-dense), ``tokens`` (1, chunk_pad) int32 and ``lane`` /
        ``start`` / ``chunk_valid`` traced int32 scalars — one XLA program
        per distinct view bucket, not per chunk index. Next-token logits
        live at ``logits[0, chunk_valid - 1]``."""
        if chunk_pad % self.block_size:
            raise ValueError("chunk_pad must be a block_size multiple")
        infos, treedef = self.infos, self.treedef
        paged, bs = self.paged, self.block_size
        cb = chunk_pad // bs
        max_seq = self.max_seq

        def prefill_chunk(storage, params, table_row, tokens, lane, start,
                          chunk_valid):
            views = []
            for arr, info in zip(storage, infos):
                if paged and info.seq_axis is not None:
                    views.append(self._gather_leaf(arr, info, table_row)[0])
                else:
                    views.append(
                        jax.lax.dynamic_index_in_dim(arr, lane, 0, False)
                    )
            cache = jax.tree_util.tree_unflatten(treedef, views)
            logits, new_cache = chunk_fn(
                params, cache, tokens, start, chunk_valid
            )
            new_leaves = jax.tree_util.tree_leaves(new_cache)
            out = []
            for arr, new, view, info in zip(storage, new_leaves, views, infos):
                j = info.seq_axis
                if j is None:
                    out.append(jax.lax.dynamic_update_index_in_dim(
                        arr, new.astype(arr.dtype), lane, 0
                    ))
                    continue
                if not paged:
                    # Lane-dense seq leaf: merge the chunk into the lane's
                    # full row. A clamp-prone dynamic_update_slice would
                    # smear a tail chunk backwards over committed rows, so
                    # gather/where instead: row positions in
                    # [start, start + chunk_valid) take the chunk's rows.
                    idx = jnp.arange(max_seq)
                    gidx = jnp.clip(idx - start, 0, chunk_pad - 1)
                    moved = jnp.take(new, gidx, axis=j)
                    keep = (idx >= start) & (idx < start + chunk_valid)
                    keep = keep.reshape(
                        (1,) * j + (max_seq,) + (1,) * (new.ndim - j - 1)
                    )
                    merged = jnp.where(keep, moved, view).astype(arr.dtype)
                    out.append(jax.lax.dynamic_update_index_in_dim(
                        arr, merged, lane, 0
                    ))
                    continue
                # Pool leaf: the chunk's cb blocks scatter to the lane's
                # table slots start//bs .. start//bs + cb - 1. The wrapper
                # pads the sliced table row with cb ZERO_BLOCK columns, so
                # this dynamic_slice can never clamp backwards; slots past
                # the chunk's valid blocks are redirected to ZERO_BLOCK
                # (dumped, then re-zeroed) instead of clobbering pool data.
                shape = new.shape
                split = new.reshape(*shape[:j], cb, bs, *shape[j + 1:])
                ids = jax.lax.dynamic_slice(
                    table_row[0], (start // bs,), (cb,)
                )
                nvb = -(-chunk_valid // bs)  # traced ceil-div
                ids = jnp.where(jnp.arange(cb) < nvb, ids, ZERO_BLOCK)
                pre = (slice(None),) * j
                pool = arr.at[(*pre, ids)].set(
                    fit_lanes(split.astype(arr.dtype), arr.shape[-1]))
                pool = pool.at[(*pre, ZERO_BLOCK)].set(
                    jnp.zeros_like(pool[(*pre, ZERO_BLOCK)])
                )
                out.append(pool)
            return logits, out

        jitted = jax.jit(prefill_chunk, donate_argnums=(0,))

        def call(storage, table_row, tokens, lane, start, chunk_valid):
            if paged:
                row = np.asarray(table_row, np.int32).reshape(1, -1)
                row = np.concatenate(
                    [row, np.full((1, cb), ZERO_BLOCK, np.int32)], axis=1
                )
            else:
                row = np.zeros((1, 1), np.int32)
            return jitted(
                storage, params, jnp.asarray(row),
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lane, jnp.int32), jnp.asarray(start, jnp.int32),
                jnp.asarray(chunk_valid, jnp.int32),
            )

        call._jitted = jitted  # jit-cache probe for telemetry/accounting.py
        return call

    def dense_snapshot(self, lane: int) -> list[np.ndarray]:
        """Host copies of a lane's dense (non-pooled) leaves — the carried
        landmark/streaming prefill state of a lane being parked mid-chunked-
        prefill (its pool blocks stay allocated; only the dense carry needs
        saving because the lane's dense slots get reused)."""
        return [
            np.asarray(self._storage[idx][lane])
            for idx, info in enumerate(self.infos)
            if not (self.paged and info.seq_axis is not None)
        ]

    def dense_restore(self, lane: int, snap: list[np.ndarray]) -> None:
        """Reinstall a ``dense_snapshot`` into ``lane`` (resume a parked
        mid-prefill request at its completed-chunk boundary)."""
        it = iter(snap)
        for idx, info in enumerate(self.infos):
            if self.paged and info.seq_axis is not None:
                continue
            self._storage[idx] = self._storage[idx].at[lane].set(
                jnp.asarray(next(it))
            )

    def make_rebase_step(self, vmapped_rebase):
        """Jitted frozen-mode boundary rebase (serve/decode_state.py):
        gather lane views from the pool -> vmapped ``rebase_streaming`` ->
        commit the lane-dense streaming-stat leaves of flagged lanes. The
        paged K/V pool is read (the rebase recomputes two landmark rows over
        the horizon) but never written, so only dense leaves commit.

        Returns ``fn(storage, tables, positions, flags, n_view_blocks) ->
        new_storage``; like ``make_fused_step``, one XLA program compiles
        per distinct ``n_view_blocks`` and pool buffers are donated."""
        infos, treedef = self.infos, self.treedef
        paged = self.paged
        n_lanes = self.max_lanes

        def rebase(storage, tables, positions, flags):
            views = [
                arr if (not paged or info.seq_axis is None)
                else self._gather_leaf(arr, info, tables)
                for arr, info in zip(storage, infos)
            ]
            cache = jax.tree_util.tree_unflatten(treedef, views)
            new_cache = vmapped_rebase(cache, positions)
            new_leaves = jax.tree_util.tree_leaves(new_cache)
            out = []
            for arr, new, info in zip(storage, new_leaves, infos):
                if not paged or info.seq_axis is None:
                    mask = flags.reshape((n_lanes,) + (1,) * (arr.ndim - 1))
                    out.append(jnp.where(mask, new.astype(arr.dtype), arr))
                else:
                    out.append(arr)
            return out

        jitted = jax.jit(rebase, donate_argnums=(0,))

        def call(storage, tables, positions, flags, n_view_blocks):
            if self.paged:
                tables = tables[:, :n_view_blocks]
            return jitted(storage, tables, positions, flags)

        call._jitted = jitted  # jit-cache probe for telemetry/accounting.py
        return call

    def view_blocks_needed(self, positions, lanes, quantum: int = 0) -> int:
        """Bucketed block count covering the deepest active position — one
        compiled tick program per distinct result. ``quantum`` > 0 (a
        measured ``Plan.block_table``) rounds up to that multiple instead
        of the next power of two."""
        if not self.paged or not lanes:
            return self.max_seq // self.block_size
        need = max(int(positions[i]) // self.block_size + 1 for i in lanes)
        return bucket_view_slots(
            need, self.max_seq // self.block_size, quantum
        )

    def zero_lane_dense(self, lane: int) -> None:
        """Fresh-request reset of a lane's dense (non-paged) state."""
        for idx, info in enumerate(self.infos):
            if self.paged and info.seq_axis is not None:
                continue
            self._storage[idx] = self._storage[idx].at[lane].set(
                jnp.zeros_like(self._storage[idx][lane])
            )

    def copy_block(self, src: int, dst: int) -> None:
        """Copy one pool block's token rows in every pooled leaf — the
        device half of copy-on-write, run once when a shared block gets its
        first divergent write (``BlockAllocator.cow`` does the host half)."""
        if not self.paged:
            return
        for idx, info in enumerate(self.infos):
            j = info.seq_axis
            if j is None:
                continue
            arr = self._storage[idx]
            pre = (slice(None),) * j
            self._storage[idx] = arr.at[(*pre, dst)].set(arr[(*pre, src)])

    def apply_mapping(self, mapping: dict[int, int]) -> None:
        """Permute pool storage after ``BlockAllocator.defragment``."""
        if not mapping or not self.paged:
            return
        old = jnp.asarray(list(mapping.keys()), jnp.int32)
        new = jnp.asarray(list(mapping.values()), jnp.int32)
        for idx, info in enumerate(self.infos):
            if info.seq_axis is None:
                continue
            arr = self._storage[idx]
            pre = (slice(None),) * info.seq_axis
            self._storage[idx] = arr.at[(*pre, new)].set(arr[(*pre, old)])
