"""Streaming decode state: per-landmark online-softmax stats in the KV cache.

The only n-sized object in spectral-shift decode is the landmark-to-key
matrix ``B = softmax(Q~ K^T)`` and its value summary ``BV``. The legacy
(``decode_streaming="recompute"``) path rebuilds both over the whole cache
horizon every token — O(c*S*d) per tick — which forfeits the paper's O(n)
total-cost claim exactly where it matters. This module makes the linear
term *streamed*: the cache carries, per landmark row r, the online-softmax
partial state

    bv_m   (B, H, c, 1)   row anchor m_r        (a valid, not necessarily
                                                 maximal, exp anchor)
    bv_l   (B, H, c, 1)   l_r   = sum_j exp(s_rj - m_r)
    bv_acc (B, H, c, dv)  acc_r = sum_j exp(s_rj - m_r) * v_j

so ``BV[r] = acc_r / l_r``. The zeros state (0, 0, 0) is a valid empty
partial (the anchor need not be the true max — any finite anchor yields the
same normalized result), which lets the leaves share the cache's zeros
init, ``zero_lane_dense`` reset and prefill overwrite without a sentinel.

Per decode tick (``ss_decode_attention_streaming``):

* every *frozen* landmark row (segments before the active one — their
  landmark mean no longer moves) absorbs the new key/value with the shared
  flash-append (``kernels.ops.flash_merge``, the same algebra the
  context-parallel driver merges shards with): O(c*d) total;
* the *active* segment's row — whose landmark mean still drifts with each
  new token — is handled by ``ModelConfig.decode_streaming``:
    - ``"exact"``: recompute that one row over keys 0..pos every tick
      (O(S*d); a c-fold win over recompute, and mathematically identical to
      it — every stored row equals the softmax of today's landmark means);
    - ``"frozen"``: the active row streams too, scoring each key with the
      mean current at append time (bounded drift within one segment), and
      is *rebased* — exactly recomputed — at segment boundaries by
      ``rebase_streaming`` (the engine triggers it; amortized O(c*d)/token).

Invariant: rows past the active segment hold the zero state (appends are
row-masked, prefill seeding masks them), so they contribute nothing until
they become active and are founded by the exact recompute / rebase.

Prefill seeds these leaves in one shot (serve/prefill.py): the ``ss_fused``
path streams the prompt through the ``landmark_summary`` kernel once with
the cache's horizon-segmented landmark means and hands the kernel's
(m, l, BV) directly into the cache; the replay path uses the jnp
``recompute_stats``. Scheduler preemption recomputes through the same
prefill path on re-admission, so a preempted request's streaming state is
rebuilt exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.landmarks import onehot_segment_sums, segment_counts
from repro.core.pinv import CORE_PRECISION
from repro.core.spectral_shift import ss_core
from repro.kernels.ops import flash_merge

NEG_INF = -1e30

# Cache-leaf names of the streaming state, in every attention layer cache.
STREAM_LEAVES = ("bv_m", "bv_l", "bv_acc")

DECODE_STREAMING_MODES = ("recompute", "exact", "frozen")


# --------------------------------------------------------------------------
# Landmark bookkeeping (shared with serve/decode.py and serve/prefill.py;
# backed by the core/landmarks helpers so the formulas cannot drift).
# --------------------------------------------------------------------------
def segment_len(seq_max: int, c: int) -> int:
    return -(-seq_max // c)


def landmark_counts(pos: jnp.ndarray, seq_max: int, c: int) -> jnp.ndarray:
    """Tokens accumulated per landmark after ``pos+1`` tokens. (c,) fp32;
    zero for segments not yet reached (floor=0 keeps validity derivable)."""
    return segment_counts(pos + 1, c, segment_len(seq_max, c), floor=0)


def lmk_add(sums: jnp.ndarray, value: jnp.ndarray, pos: jnp.ndarray,
            seq_max: int) -> jnp.ndarray:
    """sums (..., c, d) += value (..., d) routed to segment(pos) — the
    single-token case of the shared ``onehot_segment_sums`` GEMM."""
    c = sums.shape[-2]
    seg = pos // segment_len(seq_max, c)
    onehot = jax.nn.one_hot(seg, c, dtype=value.dtype)[:, None]  # (c, 1)
    return sums + onehot_segment_sums(value[..., None, :], onehot).astype(
        sums.dtype
    )


def landmark_means(sums: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """fp32 means of running landmark sums; empty segments divide by 1."""
    return sums.astype(jnp.float32) / jnp.maximum(counts, 1.0)[:, None]


def masked_softmax(scores, mask):
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.where(mask, p, 0.0)
    return p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)


# --------------------------------------------------------------------------
# Streaming-stat primitives.
# --------------------------------------------------------------------------
def stream_append(stats, q_l, k_new, v_new, scale: float, row_mask=None):
    """Flash-append one key/value to every landmark row's partial state.

    stats = (m, l, acc) with shapes (B, H, c, 1)/(B, H, c, 1)/(B, H, c, dv);
    q_l (B, H, c, d) fp32 landmark means; k_new (B, H, d); v_new (B, H, dv).
    The new element's own partial is (m=s, l=1, acc=v); ``row_mask`` (c,)
    bool keeps masked-out rows (segments not yet reached) untouched."""
    m, l, acc = (x.astype(jnp.float32) for x in stats)
    s = jnp.einsum(
        "bhcd,bhd->bhc", q_l, k_new.astype(jnp.float32)
    )[..., None] * scale                                   # (B, H, c, 1)
    m_n, l_n, acc_n = flash_merge(
        m, l, acc, s, jnp.ones_like(s),
        v_new[:, :, None, :].astype(jnp.float32),
    )
    if row_mask is not None:
        rm = row_mask[:, None]
        m_n = jnp.where(rm, m_n, m)
        l_n = jnp.where(rm, l_n, l)
        acc_n = jnp.where(rm, acc_n, acc)
    return m_n, l_n, acc_n


def recompute_stats(q_l, k, v, pos, scale: float, row_valid=None):
    """Exact (m, l, acc) of ``softmax(scale * q_l . K[0..pos])`` rows.

    q_l (B, H, c, d); k/v (B, H, S, d/dv); keys past ``pos`` masked out.
    ``row_valid`` (c,) bool zeroes rows for segments not yet reached, so
    the streaming invariant (future rows == zero state) holds."""
    s = jnp.einsum(
        "bhcd,bhsd->bhcs", q_l.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    key_mask = (jnp.arange(k.shape[2]) <= pos)[None, None, None, :]
    s = jnp.where(key_mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(key_mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhcs,bhsd->bhcd", p, v.astype(jnp.float32))
    if row_valid is not None:
        rv = row_valid[:, None]
        m = jnp.where(rv, m, 0.0)
        l = jnp.where(rv, l, 0.0)
        acc = jnp.where(rv, acc, 0.0)
    return m, l, acc


def rebase_rows(stats, q_l, k, v, pos, scale: float, rows):
    """Exactly recompute the partial state of the (distinct) landmark rows
    ``rows`` ((R,) int32, possibly traced) over keys 0..pos; other rows pass
    through unchanged. O(R*S*d) — the amortized cost of the frozen mode."""
    m, l, acc = stats
    c = q_l.shape[2]
    q_sel = jnp.take(q_l, rows, axis=2)                   # (B, H, R, d)
    m_r, l_r, acc_r = recompute_stats(q_sel, k, v, pos, scale)
    onehot = (rows[:, None] == jnp.arange(c)[None, :]).astype(jnp.float32)
    hit = (jnp.sum(onehot, axis=0) > 0)[:, None]          # (c, 1)

    def put(old, new):
        upd = jnp.einsum("rc,bhrx->bhcx", onehot, new)
        return jnp.where(hit, upd, old.astype(jnp.float32))

    return put(m, m_r), put(l, l_r), put(acc, acc_r)


def rebase_span(stats, q_l, k, v, pos, scale: float, row_lo, row_hi,
                span: int):
    """Exactly recompute a *contiguous* window of landmark rows
    ``row_lo..row_hi`` (traced scalars) over keys 0..pos; other rows pass
    through unchanged. ``span`` is the static window capacity
    (``row_hi - row_lo + 1 <= span``); rows past ``row_hi`` or ``c`` are
    masked out of the scatter, so the window may hang off either bound.

    This is ``rebase_rows`` for the chunked-prefill case where the row set
    is a traced range rather than concrete indices: consecutive rows are
    distinct by construction, and the clamped tail duplicates are masked,
    so the onehot scatter never double-adds (``rebase_rows`` would)."""
    m, l, acc = stats
    c = q_l.shape[2]
    rows = row_lo + jnp.arange(span)                      # (span,) traced
    q_sel = jnp.take(q_l, jnp.minimum(rows, c - 1), axis=2)
    m_r, l_r, acc_r = recompute_stats(q_sel, k, v, pos, scale)
    live = (rows <= row_hi) & (rows < c)
    onehot = (
        (rows[:, None] == jnp.arange(c)[None, :]) & live[:, None]
    ).astype(jnp.float32)
    hit = (jnp.sum(onehot, axis=0) > 0)[:, None]          # (c, 1)

    def put(old, new):
        upd = jnp.einsum("rc,bhrx->bhcx", onehot, new)
        return jnp.where(hit, upd, old.astype(jnp.float32))

    return put(m, m_r), put(l, l_r), put(acc, acc_r)


def mask_stats_rows(stats, keep):
    """Zero the partial state of rows where ``keep`` (c,) is False."""
    m, l, acc = stats
    km = keep[:, None]
    return (
        jnp.where(km, m, 0.0),
        jnp.where(km, l, 0.0),
        jnp.where(km, acc, 0.0),
    )


# --------------------------------------------------------------------------
# The streaming decode attention step.
# --------------------------------------------------------------------------
def ss_decode_attention_streaming(
    q: jnp.ndarray,        # (B, H, 1, d)
    k_new: jnp.ndarray,    # (B, H, d)   this tick's key (heads broadcast)
    v_new: jnp.ndarray,    # (B, H, dv)  this tick's value
    k_cache,               # (B, Hkv, S, d) view incl. the new key at ``pos``
                           # — or None on the gather-free paged route
    v_cache,               # (B, Hkv, S, dv) (raw KV heads; Hkv divides H)
    q_lmk_sum: jnp.ndarray,  # (B, H, c, d)  updated running sums
    k_lmk_sum: jnp.ndarray,  # (B, H, c, d)
    stats,                 # (bv_m, bv_l, bv_acc) pre-append cache leaves
    pos: jnp.ndarray,      # scalar int32: index of the current token
    cfg: ModelConfig,
    scale: float,
    seq_max: int | None = None,
    mode: str = "exact",
    active_stats_fn=None,
):
    """One spectral-shift decode step with streamed B-side state.

    Same output formula as ``ss_decode_attention`` — F U_ss BV + delta*v —
    but BV comes from the cached (m, l, acc) stats instead of an O(c*S*d)
    recompute. Returns ``(out (B, H, 1, dv), (m, l, acc))``; the caller
    commits the new stats to the cache. ``k_cache``/``v_cache`` are only
    read by the ``"exact"`` active-row recompute (the ``"frozen"`` tick
    never touches the horizon) and are taken with their RAW kv-head count —
    the per-query-head active rows group onto the kv heads, so no
    O(H*S*d) head-broadcast is ever materialized on the hot path.

    ``active_stats_fn`` (optional) REPLACES that dense active-row
    recompute: called with the active landmark-mean row ``q_act``
    (B, H, 1, d), it must return the exact softmax partials over keys
    ``0..pos`` as ``(m (B,H,1,1), l (B,H,1,1), acc (B,H,1,dv))``. The
    gather-free paged route (serve/decode.py) supplies a closure over the
    block-table Pallas kernel here, with ``k_cache``/``v_cache`` = None —
    no dense horizon view ever exists on that route."""
    if mode not in ("exact", "frozen"):
        raise ValueError(
            f"unknown decode_streaming mode {mode!r}; want 'exact' or "
            f"'frozen' (or route 'recompute' to ss_decode_attention)"
        )
    if k_cache is None:
        if seq_max is None:
            raise ValueError("k_cache=None (paged route) requires seq_max")
        if mode == "exact" and active_stats_fn is None:
            raise ValueError(
                "exact mode without a cache view needs active_stats_fn"
            )
        s_max = seq_max
    else:
        s_len = k_cache.shape[2]
        s_max = s_len if seq_max is None else seq_max
    c = q_lmk_sum.shape[2]
    counts = landmark_counts(pos, s_max, c)
    valid = counts > 0
    q_l = landmark_means(q_lmk_sum, counts)
    k_l = landmark_means(k_lmk_sum, counts)

    f = masked_softmax(
        jnp.einsum("bhqd,bhcd->bhqc", q.astype(jnp.float32), k_l) * scale,
        valid[None, None, None, :],
    )  # (B, H, 1, c)
    a_mask = valid[None, None, :, None] & valid[None, None, None, :]
    a_raw = masked_softmax(
        jnp.einsum("bhcd,bhed->bhce", q_l, k_l, precision=CORE_PRECISION)
        * scale,
        a_mask,
    )
    eye = jnp.eye(c, dtype=jnp.float32)
    a = jnp.where(a_mask, a_raw, eye)  # invalid block pinned to identity
    core = ss_core(
        a, method="iterative", pinv_iters=cfg.pinv_iters,
        use_shift=cfg.include_shift_identity,
    )

    active = pos // segment_len(s_max, c)
    m, l, acc = stream_append(
        stats, q_l, k_new, v_new, scale, row_mask=jnp.arange(c) <= active
    )
    if mode == "exact":
        # The active segment's landmark mean moved with this token, so its
        # whole row of scores is stale: recompute that ONE row exactly.
        # Query heads group onto the raw kv heads (GQA) so the einsums run
        # against the cache as stored instead of a broadcast copy.
        b, h = q_l.shape[:2]
        q_act = jax.lax.dynamic_slice_in_dim(q_l, active, 1, axis=2)
        if active_stats_fn is not None:
            m_a, l_a, acc_a = active_stats_fn(q_act)
        else:
            hkv = k_cache.shape[1]
            q_g = q_act.reshape(b, hkv, h // hkv, q_l.shape[-1])
            m_a, l_a, acc_a = recompute_stats(q_g, k_cache, v_cache, pos,
                                              scale)
            m_a = m_a.reshape(b, h, 1, 1)
            l_a = l_a.reshape(b, h, 1, 1)
            acc_a = acc_a.reshape(b, h, 1, acc.shape[-1])
        hit = (jnp.arange(c) == active)[:, None]          # (c, 1)
        m = jnp.where(hit, m_a, m)
        l = jnp.where(hit, l_a, l)
        acc = jnp.where(hit, acc_a, acc)

    bv = acc / jnp.maximum(l, 1e-30)                      # (B, H, c, dv)
    ubv = jnp.einsum("bhce,bhed->bhcd", core.u, bv, precision=CORE_PRECISION)
    out = jnp.einsum("bhqc,bhcd->bhqd", f, ubv)
    if cfg.include_shift_identity:
        out = out + core.delta * v_new[:, :, None, :].astype(jnp.float32)
    return out.astype(q.dtype), (m, l, acc)


# --------------------------------------------------------------------------
# Frozen-mode lazy rebase (engine-triggered at segment boundaries).
# --------------------------------------------------------------------------
def _rebase_attn_layer(cfg: ModelConfig, lcache: dict, pos, seq_max, mla):
    """Recompute rows {active-1, active} of one attention layer's streaming
    stats from its cached K/V view. ``pos`` is the boundary position just
    written (pos % seg == 0, pos > 0): row active-1 just froze with its
    final landmark mean (clearing the drift its active phase accumulated),
    and row active is founded over the whole horizon so subsequent appends
    extend an exact base."""
    from repro.models.attention import _broadcast_kv

    c = cfg.num_landmarks
    if mla:
        s_len = lcache["latent"].shape[1]
        h = cfg.num_heads
        k_eff = jnp.concatenate(
            [lcache["latent"], lcache["rope"]], axis=-1
        )[:, None]                                        # (B, 1, S, de)
        kb = jnp.broadcast_to(k_eff, (k_eff.shape[0], h, *k_eff.shape[2:]))
        lat = lcache["latent"][:, None]
        vb = jnp.broadcast_to(lat, (lat.shape[0], h, *lat.shape[2:]))
        scale = (cfg.resolved_head_dim + cfg.rope_head_dim) ** -0.5
    else:
        s_len = lcache["k"].shape[2]
        kb = _broadcast_kv(lcache["k"], cfg.num_heads)
        vb = _broadcast_kv(lcache["v"], cfg.num_heads)
        scale = cfg.resolved_head_dim ** -0.5
    s_max = s_len if seq_max is None else seq_max
    counts = landmark_counts(pos, s_max, c)
    q_l = landmark_means(lcache["q_lmk"], counts)
    active = pos // segment_len(s_max, c)
    rows = jnp.stack([jnp.maximum(active - 1, 0), active])
    stats = tuple(lcache[name] for name in STREAM_LEAVES)
    m, l, acc = rebase_rows(stats, q_l, kb, vb, pos, scale, rows)
    return dict(lcache, bv_m=m, bv_l=l, bv_acc=acc)


def rebase_streaming(cfg: ModelConfig, cache, pos, seq_max=None):
    """Apply the frozen-mode boundary rebase to every attention layer of a
    decode cache tree (dense views; the paged engine gathers first — see
    ``PagedKVCache.make_rebase_step``). No-op for attention-free stacks."""
    if cfg.family == "ssm":
        return cache

    def one(lc):
        if cfg.family == "hybrid":
            return dict(
                lc,
                attn=_rebase_attn_layer(cfg, lc["attn"], pos, seq_max, False),
            )
        return _rebase_attn_layer(cfg, lc, pos, seq_max, cfg.mla)

    layers = cache["layers"]
    if isinstance(layers, list):
        new_layers = [one(lc) for lc in layers]
    else:
        new_layers = jax.vmap(one)(layers)  # scan_layers: stacked leaves
    return dict(cache, layers=new_layers)


def make_rebase_fn(cfg: ModelConfig, seq_max: int):
    """Boundary-rebase closure ``fn(cache, pos) -> cache`` (vmap-ready)."""

    def fn(cache, pos):
        return rebase_streaming(cfg, cache, pos, seq_max=seq_max)

    return fn


# --------------------------------------------------------------------------
# Prefix-cache attach: landmark-sum re-segmentation + full stat reseed.
#
# A cached prefix's streaming stats are only valid at the segmentation they
# were computed under (the horizon ``seq_max`` and landmark count ``c`` fix
# ``segment_len``). Within one engine every lane shares that segmentation,
# so a "reseg" attach is a pure host-side passthrough of the cached dense
# snapshot — bitwise identical to the state a cold prefill would have left,
# which is what keeps frozen-mode outputs greedy-identical. When the cached
# segmentation DIFFERS (a cross-engine cache, or ``prefix_attach=
# "recompute"`` forcing re-derivation), the functions below rebuild the
# canonical state from what the shared blocks + snapshot actually carry:
#
# * the landmark running SUMS re-segment exactly whenever each target
#   window is a union of source windows (``seg_to % seg_from == 0`` — the
#   canonical storage segmentation is the finer one), as one O(c^2*d)
#   routing GEMM (``resegment_sums``, generalizing the ``rebase_span``
#   scatter from a row window to a row *regrouping*);
# * the per-row softmax partials (m, l, acc) cannot be merged across rows
#   (each row scores with its own landmark mean), so they are re-founded
#   exactly over the shared K/V via ``recompute_stats`` — the same math the
#   prefill handoff seeds them with, token-identity-tested against it.
# --------------------------------------------------------------------------
def resegment_sums(sums: jnp.ndarray, seg_from: int, seg_to: int):
    """Re-segment per-landmark running sums (..., c, d) from segment length
    ``seg_from`` to ``seg_to``. Exact when every target window is a union
    of source windows (``seg_to % seg_from == 0``: target row t is the sum
    of source rows t*m..(t+1)*m-1, m = seg_to/seg_from; source rows past c
    hold zeros by the streaming invariant, so truncation loses nothing up
    to the source horizon). Coarse-to-fine is information-lossy and
    rejected — re-derive through the prefill path instead."""
    if seg_to == seg_from:
        return sums
    if seg_to % seg_from:
        raise ValueError(
            f"cannot re-segment sums from segment length {seg_from} to "
            f"{seg_to}: target windows must be unions of source windows "
            f"(seg_to % seg_from == 0)"
        )
    c = sums.shape[-2]
    m = seg_to // seg_from
    route = (
        (jnp.arange(c)[:, None] // m) == jnp.arange(c)[None, :]
    ).astype(jnp.float32)                                  # (c_src, c_tgt)
    return jnp.einsum(
        "sc,...sd->...cd", route, sums.astype(jnp.float32)
    ).astype(sums.dtype)


def _reseed_attn_layer(cfg: ModelConfig, lcache: dict, pos, seq_max, mla,
                       seg_from):
    """Re-found one attention layer's streaming state at the canonical
    segmentation: re-segment the landmark sums if the source segmentation
    differs, then exactly recompute EVERY reached row's (m, l, acc) over
    keys 0..pos — ``_rebase_attn_layer`` generalized from the two boundary
    rows to the full row set (the whole prefix is new to this lane)."""
    from repro.models.attention import _broadcast_kv

    c = cfg.num_landmarks
    if mla:
        s_len = lcache["latent"].shape[1]
        h = cfg.num_heads
        k_eff = jnp.concatenate(
            [lcache["latent"], lcache["rope"]], axis=-1
        )[:, None]                                        # (B, 1, S, de)
        kb = jnp.broadcast_to(k_eff, (k_eff.shape[0], h, *k_eff.shape[2:]))
        lat = lcache["latent"][:, None]
        vb = jnp.broadcast_to(lat, (lat.shape[0], h, *lat.shape[2:]))
        scale = (cfg.resolved_head_dim + cfg.rope_head_dim) ** -0.5
    else:
        s_len = lcache["k"].shape[2]
        kb = _broadcast_kv(lcache["k"], cfg.num_heads)
        vb = _broadcast_kv(lcache["v"], cfg.num_heads)
        scale = cfg.resolved_head_dim ** -0.5
    s_max = s_len if seq_max is None else seq_max
    seg_to = segment_len(s_max, c)
    q_sum, k_sum = lcache["q_lmk"], lcache["k_lmk"]
    if seg_from is not None and seg_from != seg_to:
        q_sum = resegment_sums(q_sum, seg_from, seg_to)
        k_sum = resegment_sums(k_sum, seg_from, seg_to)
    counts = landmark_counts(pos, s_max, c)
    q_l = landmark_means(q_sum, counts)
    m, l, acc = recompute_stats(q_l, kb, vb, pos, scale,
                                row_valid=counts > 0)
    return dict(lcache, q_lmk=q_sum, k_lmk=k_sum, bv_m=m, bv_l=l,
                bv_acc=acc)


def reseed_streaming(cfg: ModelConfig, cache, pos, seq_max=None,
                     seg_from=None):
    """Re-found every attention layer's streaming stats from its cached K/V
    at the canonical segmentation (dense views; the paged engine gathers
    first through ``PagedKVCache.make_rebase_step``). ``pos`` is the index
    of the LAST attached token. ``seg_from`` re-segments the landmark sums
    when the snapshot was stored under a different segment length. No-op
    for attention-free stacks."""
    if cfg.family == "ssm":
        return cache

    def one(lc):
        if cfg.family == "hybrid":
            return dict(
                lc,
                attn=_reseed_attn_layer(cfg, lc["attn"], pos, seq_max,
                                        False, seg_from),
            )
        return _reseed_attn_layer(cfg, lc, pos, seq_max, cfg.mla, seg_from)

    layers = cache["layers"]
    if isinstance(layers, list):
        new_layers = [one(lc) for lc in layers]
    else:
        new_layers = jax.vmap(one)(layers)  # scan_layers: stacked leaves
    return dict(cache, layers=new_layers)


def make_reseed_fn(cfg: ModelConfig, seq_max: int, seg_from=None):
    """Attach-reseed closure ``fn(cache, pos) -> cache`` (vmap-ready; rides
    the same ``make_rebase_step`` plumbing as the boundary rebase — pool
    K/V is read, only the lane-dense leaves commit)."""

    def fn(cache, pos):
        return reseed_streaming(cfg, cache, pos, seq_max=seq_max,
                                seg_from=seg_from)

    return fn
