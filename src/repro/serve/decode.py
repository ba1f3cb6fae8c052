"""Single-token decode step against a KV cache, with the paper's
spectral-shifting approximation as the decode-attention engine.

Decode is the setting where the method applies *exactly* (a single query
attending to all past keys has no causal-mask conflict, DESIGN.md §2.4).
Landmark means are maintained incrementally in the cache as running sums;
per-landmark counts derive from ``pos`` so nothing goes stale.

For each layer the spectral-shift decode computes

    F = L(q K~^T)          (B,H,1,c)     O(c d)
    A = L(Q~ K~^T)         (B,H,c,c)     O(c^2 d)
    B = L(Q~ K_cache^T)    (B,H,c,S)     O(c S d)   <- the linear term
    out = F U_ss (B V) + delta * v_new

Empty landmarks (segments not yet reached) are masked out of F/B and pinned
to identity rows/cols of A so the pseudoinverse is well-posed.

``ModelConfig.decode_streaming`` selects how the linear term is obtained:
``"recompute"`` is the O(c*S*d)-per-token path above; ``"exact"``/``"frozen"``
stream per-landmark online-softmax stats carried in the cache instead
(serve/decode_state.py) — same output formula, the B/BV rebuild replaced by
an O(c*d) flash-append plus (exact mode) a single-row recompute.

Gather-free paged decode (``ServeConfig.decode_impl="paged"``): when
``decode_step`` receives ``paged_table``/``paged_meta``, the seq-shaped
cache leaves ARE the shared block pools (broadcast unbatched through the
engine's lane vmap; layout ``(..., num_blocks, block_size, ...)`` with the
block pair sitting where ``cache_seq`` was). Attention layers then

* never write the pools — each layer returns the new token's K/V (seq axis
  of length 1) and ``PagedKVCache.make_paged_step`` commits it with a
  single-block scatter after the step;
* read the horizon (exact-mode active row, ``full`` decode attention) only
  through the block-table Pallas kernel (kernels/paged_decode.py), whose
  partials over keys ``0..pos-1`` are flash-merged with the current token.

``decode_streaming="frozen"`` ticks therefore touch no horizon bytes at
all; ``"recompute"`` needs the dense B matrix and stays on the gather
route (the engine enforces the fallback).
"""
from __future__ import annotations

import functools
import itertools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.pinv import CORE_PRECISION
from repro.core.spectral_shift import ss_core
from repro.models.layers import (
    apply_rotary,
    layer_norm,
    mlp_forward,
    rms_norm,
    rotary_angles,
    sinusoidal_positions,
)
from repro.models.model import _embed_tokens, _unembed
from repro.models.moe import moe_forward
from repro.models.ssm import mlstm_step
from repro.models.attention import _broadcast_kv
from repro.serve.decode_state import (
    STREAM_LEAVES,
    landmark_counts,
    landmark_means,
    lmk_add,
    masked_softmax as _masked_softmax,
    segment_len,
    ss_decode_attention_streaming,
)

Cache = Any

# Landmark bookkeeping now lives in serve/decode_state.py (backed by the
# shared core/landmarks helpers); these aliases keep the historical import
# surface of this module intact.
_segment_len = segment_len
_landmark_counts = landmark_counts
_lmk_add = lmk_add


def ss_decode_attention(
    q: jnp.ndarray,        # (B, H, 1, d)
    k_cache: jnp.ndarray,  # (B, H, S, d)   (kv heads already broadcast)
    v_cache: jnp.ndarray,  # (B, H, S, dv)
    q_lmk_sum: jnp.ndarray,  # (B, H, c, d)
    k_lmk_sum: jnp.ndarray,  # (B, H, c, d)
    pos: jnp.ndarray,      # scalar int32: index of the current token
    cfg: ModelConfig,
    scale: float,
    seq_max: int | None = None,  # landmark segmentation horizon; defaults to
                                 # the cache view length. Batched prefill
                                 # passes the lane's full max_seq so segment
                                 # routing matches later decode steps even
                                 # though its K/V view is only prompt-long.
) -> jnp.ndarray:
    s_max = k_cache.shape[2]  # view length; the landmark horizon may differ
    c = q_lmk_sum.shape[2]
    horizon = s_max if seq_max is None else seq_max
    counts = _landmark_counts(pos, horizon, c)  # (c,) fp32
    valid = counts > 0
    q_l = landmark_means(q_lmk_sum, counts)
    k_l = landmark_means(k_lmk_sum, counts)

    f = _masked_softmax(
        jnp.einsum("bhqd,bhcd->bhqc", q.astype(jnp.float32), k_l) * scale,
        valid[None, None, None, :],
    )  # (B,H,1,c)
    a_mask = valid[None, None, :, None] & valid[None, None, None, :]
    a_raw = _masked_softmax(
        jnp.einsum("bhcd,bhed->bhce", q_l, k_l, precision=CORE_PRECISION)
        * scale,
        a_mask,
    )
    eye = jnp.eye(c, dtype=jnp.float32)
    a = jnp.where(a_mask, a_raw, eye)  # invalid block pinned to identity
    key_mask = (jnp.arange(s_max) <= pos)[None, None, None, :]
    b_mat = _masked_softmax(
        jnp.einsum("bhcd,bhsd->bhcs", q_l, k_cache.astype(jnp.float32)) * scale,
        key_mask,
    )  # (B,H,c,S)

    core = ss_core(
        a, method="iterative", pinv_iters=cfg.pinv_iters,
        use_shift=cfg.include_shift_identity,
    )
    bv = jnp.einsum("bhcs,bhsd->bhcd", b_mat, v_cache.astype(jnp.float32))
    ubv = jnp.einsum("bhce,bhed->bhcd", core.u, bv, precision=CORE_PRECISION)
    out = jnp.einsum("bhqc,bhcd->bhqd", f, ubv)
    if cfg.include_shift_identity:
        v_new = jnp.take_along_axis(
            v_cache, jnp.broadcast_to(
                pos, (*v_cache.shape[:2], 1, 1)
            ).astype(jnp.int32), axis=2,
        ).astype(jnp.float32)
        out = out + core.delta * v_new
    return out.astype(q.dtype)


def full_decode_attention(q, k_cache, v_cache, pos, scale):
    s_max = k_cache.shape[2]
    scores = jnp.einsum(
        "bhqd,bhsd->bhqs", q.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    mask = (jnp.arange(s_max) <= pos)[None, None, None, :]
    p = _masked_softmax(scores, mask)
    return jnp.einsum("bhqs,bhsd->bhqd", p, v_cache.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------------
# Gather-free paged horizon reads (kernels/paged_decode.py). ``paged`` is
# the per-layer route descriptor ``(table, block_size, interpret)``: the
# traced (n_slots,) int32 block table plus the static kernel knobs.
# --------------------------------------------------------------------------
def _paged_merged_stats(q_g, k_pools, v_pool, k_new_g, v_new_g, paged, pos,
                        scale):
    """Exact softmax partials of rows ``q_g`` (hkv, R, d) over keys
    ``0..pos``: the kernel streams the pools (which hold keys 0..pos-1 —
    the tick commits the new token after the step), the current token is
    flash-merged on top. ``k_new_g`` is the current token's key as one
    part (hkv, d_p) per key pool; their widths split ``q_g``'s features.
    The pools are stored lane-padded (serve/paged.py), so each part of the
    rows is zero-padded to its pool's width and ``acc`` is cut back to
    ``v_new_g``'s."""
    from repro.kernels.ops import flash_merge
    from repro.kernels.paged_decode import paged_row_stats
    from repro.serve.paged import fit_lanes

    table, block_size, interpret = paged
    ends = list(itertools.accumulate(k.shape[-1] for k in k_new_g))
    q_pools = jnp.concatenate([
        fit_lanes(x, p.shape[-1])
        for x, p in zip(jnp.split(q_g, ends[:-1], axis=-1), k_pools)
    ], axis=-1)
    m, l, acc = paged_row_stats(
        q_pools, k_pools, v_pool, table, pos, scale=scale,
        block_size=block_size, interpret=interpret,
    )
    acc = acc[..., :v_new_g.shape[-1]]
    k_new = jnp.concatenate(k_new_g, axis=-1)
    s_new = jnp.einsum(
        "hrd,hd->hr", q_g.astype(jnp.float32), k_new.astype(jnp.float32)
    )[..., None] * scale                                   # (hkv, R, 1)
    return flash_merge(
        m, l, acc, s_new, jnp.ones_like(s_new),
        v_new_g[:, None, :].astype(jnp.float32),
    )


def _paged_active_stats_fn(k_pools, v_pool, k_new_g, v_new_g, paged, pos,
                           scale):
    """The ``active_stats_fn`` hook for ``ss_decode_attention_streaming``:
    one-row exact recompute through the block-table kernel. ``k_new_g`` /
    ``v_new_g`` are the current token's key/value with RAW kv heads: one
    (hkv, d_p) part per key pool / (hkv, dv)."""
    hkv = v_pool.shape[0]

    def fn(q_act):  # (B=1, H, 1, d) active landmark means
        b, h = q_act.shape[:2]
        q_g = q_act.reshape(b, hkv, h // hkv, q_act.shape[-1])[0]
        m, l, acc = _paged_merged_stats(
            q_g, k_pools, v_pool, k_new_g, v_new_g, paged, pos, scale,
        )
        return (
            m.reshape(b, h, 1, 1),
            l.reshape(b, h, 1, 1),
            acc.reshape(b, h, 1, acc.shape[-1]),
        )

    return fn


def full_decode_attention_paged(q, k_pools, v_pool, k_new_g, v_new_g, paged,
                                pos, scale):
    """Exact decode attention (one query row per head) straight from the
    block pools — the gather-free form of ``full_decode_attention``, which
    also covers the degenerate <=c regime where spectral shifting reduces
    to exact attention. ``q`` (B=1, H, 1, d); output (B, H, 1, dv)."""
    b, h = q.shape[:2]
    hkv = v_pool.shape[0]
    q_g = q.astype(jnp.float32).reshape(b, hkv, h // hkv, q.shape[-1])[0]
    m, l, acc = _paged_merged_stats(
        q_g, k_pools, v_pool, k_new_g, v_new_g, paged, pos, scale,
    )
    out = acc / jnp.maximum(l, 1e-30)                      # (hkv, G, dv)
    return out.reshape(b, h, 1, out.shape[-1]).astype(q.dtype)


# --------------------------------------------------------------------------
# per-layer decode
# --------------------------------------------------------------------------
def _update_seq(cache_arr, new, pos):
    """cache (B,H,S,D) <- new (B,H,1,D) at position pos."""
    return jax.lax.dynamic_update_slice(
        cache_arr, new.astype(cache_arr.dtype), (0, 0, pos, 0)
    )


def gqa_decode(p, cfg: ModelConfig, x, cache, pos, impl, seq_max=None,
               paged=None):
    """x (B,1,D); cache {k,v,q_lmk,k_lmk}. Returns (attn_out, new_cache).

    ``seq_max`` pins the landmark segmentation horizon when the cache view
    is shorter than the lane's logical sequence (paged short views).

    ``paged`` = (table, block_size, interpret) flips the gather-free route:
    ``cache["k"]``/``cache["v"]`` are the shared block pools
    (B=1, hkv, nb, bs, d) — never written here; ``new_cache`` returns the
    NEW TOKEN's k/v (seq length 1) for the tick's single-block scatter
    commit, and horizon reads go through the block-table kernel."""
    dt = x.dtype
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhe->bhse", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhe->bhse", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhe->bhse", x, p["w_v"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(dt)[None, :, None, :]
        k = k + p["b_k"].astype(dt)[None, :, None, :]
        v = v + p["b_v"].astype(dt)[None, :, None, :]
    if cfg.rope_theta > 0:
        sin, cos = rotary_angles(pos[None, None], dh, cfg.rope_theta)
        q = apply_rotary(q, sin[None], cos[None])
        k = apply_rotary(k, sin[None], cos[None])

    new_cache = dict(cache)
    if paged is None:
        s_max = cache["k"].shape[2] if seq_max is None else seq_max
        new_cache["k"] = _update_seq(cache["k"], k, pos)
        new_cache["v"] = _update_seq(cache["v"], v, pos)
    else:
        if seq_max is None:
            raise ValueError("paged decode requires an explicit seq_max")
        s_max = seq_max
        new_cache["k"], new_cache["v"] = k, v  # new-token commits
    new_cache["q_lmk"] = _lmk_add(cache["q_lmk"], q[:, :, 0], pos, s_max)
    new_cache["k_lmk"] = _lmk_add(cache["k_lmk"], k[:, :, 0], pos, s_max)

    scale = dh**-0.5
    if paged is not None:
        k_pools, v_pool = (cache["k"][0],), cache["v"][0]  # (hkv, nb, bs, d)
        k_new_g, v_new_g = (k[0, :, 0],), v[0, :, 0]       # raw kv heads
    if impl == "spectral_shift":
        k_lmk = _broadcast_kv(new_cache["k_lmk"], cfg.num_heads)
        if cfg.decode_streaming == "recompute":
            if paged is not None:
                raise ValueError(
                    "decode_streaming='recompute' rebuilds the dense B "
                    "matrix and is only served by the gather route"
                )
            kb = _broadcast_kv(new_cache["k"], cfg.num_heads)
            vb = _broadcast_kv(new_cache["v"], cfg.num_heads)
            out = ss_decode_attention(
                q, kb, vb, new_cache["q_lmk"], k_lmk, pos, cfg, scale,
                seq_max=s_max,
            )
        else:
            k_new = _broadcast_kv(k, cfg.num_heads)[:, :, 0]  # (B, H, d)
            v_new = _broadcast_kv(v, cfg.num_heads)[:, :, 0]
            stats = tuple(cache[name] for name in STREAM_LEAVES)
            if paged is None:
                kc, vc, stats_fn = new_cache["k"], new_cache["v"], None
            else:
                kc = vc = None
                stats_fn = _paged_active_stats_fn(
                    k_pools, v_pool, k_new_g, v_new_g, paged, pos, scale,
                )
            out, new_stats = ss_decode_attention_streaming(
                q, k_new, v_new, kc, vc,
                new_cache["q_lmk"], k_lmk, stats,
                pos, cfg, scale, seq_max=s_max, mode=cfg.decode_streaming,
                active_stats_fn=stats_fn,
            )
            new_cache.update(dict(zip(STREAM_LEAVES, new_stats)))
    elif paged is not None:
        out = full_decode_attention_paged(
            q, k_pools, v_pool, k_new_g, v_new_g, paged, pos, scale,
        )
    else:
        kb = _broadcast_kv(new_cache["k"], cfg.num_heads)
        vb = _broadcast_kv(new_cache["v"], cfg.num_heads)
        out = full_decode_attention(q, kb, vb, pos, scale)
    return jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(dt)), new_cache


def mla_decode(p, cfg: ModelConfig, x, cache, pos, impl, seq_max=None,
               paged=None):
    """Absorbed MLA decode: attention runs in the (kv_lora + rope) latent
    space; values are the latents, up-projected after mixing.

    The gather-free ``paged`` route reads the latent and rope pools as two
    separate key pools (scores accumulate per pool inside the kernel — the
    O(S) ``concat`` of the dense path never materializes) with the latent
    pool doubling as the value pool."""
    dt = x.dtype
    dh, dr, r = cfg.resolved_head_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    c_kv = rms_norm(x @ p["w_dkv"].astype(dt), p["norm_kv"], cfg.norm_eps)  # (B,1,r)
    k_rope = jnp.einsum("bsd,de->bse", x, p["w_k_rope"].astype(dt))  # (B,1,dr)
    sin, cos = rotary_angles(pos[None, None], dr, cfg.rope_theta)
    k_rope = apply_rotary(k_rope[:, None], sin[None], cos[None])[:, 0]

    q_nope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_nope"].astype(dt))
    q_rope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_rope"].astype(dt))
    q_rope = apply_rotary(q_rope, sin[None], cos[None])
    q_abs = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)  # (B,H,1,r+dr)

    new_cache = dict(cache)
    if paged is None:
        new_cache["latent"] = jax.lax.dynamic_update_slice(
            cache["latent"], c_kv.astype(cache["latent"].dtype), (0, pos, 0)
        )
        new_cache["rope"] = jax.lax.dynamic_update_slice(
            cache["rope"], k_rope.astype(cache["rope"].dtype), (0, pos, 0)
        )
        s_max = cache["latent"].shape[1] if seq_max is None else seq_max
    else:
        if seq_max is None:
            raise ValueError("paged decode requires an explicit seq_max")
        new_cache["latent"], new_cache["rope"] = c_kv, k_rope  # new token
        s_max = seq_max
    k_eff_new = jnp.concatenate([c_kv, k_rope], axis=-1)[:, 0]  # (B, r+dr)
    new_cache["k_lmk"] = _lmk_add(cache["k_lmk"], k_eff_new, pos, s_max)
    new_cache["q_lmk"] = _lmk_add(cache["q_lmk"], q_eff[:, :, 0], pos, s_max)

    scale = (dh + dr) ** -0.5
    h = cfg.num_heads
    b = x.shape[0]
    if paged is None:
        k_eff = jnp.concatenate(
            [new_cache["latent"], new_cache["rope"]], axis=-1
        )[:, None]  # (B,1,S,r+dr)
        lat = new_cache["latent"][:, None]  # (B,1,S,r) as values
    else:
        # hkv=1 pools: latent (1, nb, bs, r), rope (1, nb, bs, dr), each
        # stored lane-padded; the latent pool doubles as the value pool
        # (absorbed MLA).
        k_pools = (cache["latent"][0][None], cache["rope"][0][None])
        v_pool = k_pools[0]
        k_new_g = (c_kv[0, 0][None], k_rope[0, 0][None])  # (1, r), (1, dr)
        v_new_g = c_kv[0, 0][None]                          # (1, r)
    if impl == "spectral_shift":
        k_lmk = jnp.broadcast_to(
            new_cache["k_lmk"][:, None], new_cache["q_lmk"].shape[:2] + new_cache["k_lmk"].shape[1:]
        )
        if cfg.decode_streaming == "recompute":
            if paged is not None:
                raise ValueError(
                    "decode_streaming='recompute' rebuilds the dense B "
                    "matrix and is only served by the gather route"
                )
            k_eff_b = jnp.broadcast_to(
                k_eff, (k_eff.shape[0], h, *k_eff.shape[2:])
            )
            lat_b = jnp.broadcast_to(lat, (lat.shape[0], h, *lat.shape[2:]))
            out_lat = ss_decode_attention(
                q_eff, k_eff_b, lat_b, new_cache["q_lmk"], k_lmk, pos, cfg,
                scale, seq_max=s_max,
            )
        else:
            k_new = jnp.broadcast_to(
                k_eff_new[:, None], (b, h, k_eff_new.shape[-1])
            )
            v_new = jnp.broadcast_to(c_kv[:, 0][:, None], (b, h, r))
            stats = tuple(cache[name] for name in STREAM_LEAVES)
            if paged is None:
                kc, vc, stats_fn = k_eff, lat, None
            else:
                kc = vc = None
                stats_fn = _paged_active_stats_fn(
                    k_pools, v_pool, k_new_g, v_new_g, paged, pos, scale,
                )
            out_lat, new_stats = ss_decode_attention_streaming(
                q_eff, k_new, v_new, kc, vc, new_cache["q_lmk"],
                k_lmk, stats, pos, cfg, scale, seq_max=s_max,
                mode=cfg.decode_streaming, active_stats_fn=stats_fn,
            )
            new_cache.update(dict(zip(STREAM_LEAVES, new_stats)))
    elif paged is not None:
        out_lat = full_decode_attention_paged(
            q_eff, k_pools, v_pool, k_new_g, v_new_g, paged, pos, scale,
        )
    else:
        k_eff_b = jnp.broadcast_to(k_eff, (k_eff.shape[0], h, *k_eff.shape[2:]))
        lat_b = jnp.broadcast_to(lat, (lat.shape[0], h, *lat.shape[2:]))
        out_lat = full_decode_attention(q_eff, k_eff_b, lat_b, pos, scale)
    out = jnp.einsum("bhsr,rhe->bhse", out_lat, p["w_uv"].astype(dt))
    return jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(dt)), new_cache


def mamba_decode(p, cfg: ModelConfig, x, state):
    """Single-step mamba. x (B,1,D); state {ssm_h (B,di,n), conv (B,w-1,di)}."""
    dt = x.dtype
    ui = x[:, 0] @ p["w_in"].astype(dt)  # (B, 2di)
    di = ui.shape[-1] // 2
    u, z = ui[..., :di], ui[..., di:]
    width = p["conv_w"].shape[0]
    ctx = jnp.concatenate([state["conv"].astype(dt), u[:, None]], axis=1)  # (B,w,di)
    u_conv = jnp.einsum("bwd,wd->bd", ctx, p["conv_w"].astype(dt)) + p["conv_b"].astype(dt)
    u_conv = jax.nn.silu(u_conv)
    bc = u_conv @ p["w_bc"].astype(dt)
    n = cfg.ssm_state
    b_mat, c_mat = bc[..., :n], bc[..., n:]
    dt_pre = (u_conv @ p["w_dt"].astype(dt)) @ p["w_dt_out"].astype(dt)
    delta = jax.nn.softplus(dt_pre.astype(jnp.float32) + p["b_dt"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    abar = jnp.exp(delta[..., None] * a)  # (B,di,n)
    bbar = delta[..., None] * b_mat.astype(jnp.float32)[:, None, :] * u_conv.astype(jnp.float32)[..., None]
    h_new = abar * state["ssm_h"] + bbar
    y = jnp.einsum("bdn,bn->bd", h_new, c_mat.astype(jnp.float32))
    y = y + p["d_skip"].astype(jnp.float32) * u_conv.astype(jnp.float32)
    out = (y.astype(dt) * jax.nn.silu(z)) @ p["w_out"].astype(dt)
    return out[:, None], {"ssm_h": h_new, "conv": ctx[:, 1:]}


def mlstm_block_decode(p, cfg: ModelConfig, x, state):
    b = x.shape[0]
    h = cfg.num_heads
    dt = x.dtype
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    up = xn[:, 0] @ p["w_up"].astype(dt)  # (B, 2di)
    di = up.shape[-1] // 2
    xm, z = up[..., :di], up[..., di:]
    ctx = jnp.concatenate([state["conv"].astype(dt), xm[:, None]], axis=1)
    xc = jnp.einsum("bwd,wd->bd", ctx, p["conv_w"].astype(dt)) + p["conv_b"].astype(dt)
    xc = jax.nn.silu(xc)
    to_heads = lambda a: a.reshape(b, h, di // h)
    q = to_heads(xc @ p["w_q"].astype(dt))
    k = to_heads(xc @ p["w_k"].astype(dt))
    v = to_heads(xm @ p["w_v"].astype(dt))
    gates = xc @ p["w_if"].astype(dt) + p["b_if"].astype(dt)
    ilog = gates[..., :h]
    flog = jax.nn.log_sigmoid(gates[..., h:].astype(jnp.float32))
    core, (c_n, n_n, m_n) = mlstm_step(q, k, v, ilog, flog,
                                       (state["c"], state["n"], state["m"]))
    core = rms_norm(core.reshape(b, di), p["ln_inner"], cfg.norm_eps)
    out = (core * jax.nn.silu(z)) @ p["w_down"].astype(dt)
    return x + out[:, None], {"c": c_n, "n": n_n, "m": m_n, "conv": ctx[:, 1:]}


def slstm_block_decode(p, cfg: ModelConfig, x, state):
    b = x.shape[0]
    h = cfg.num_heads
    dh = cfg.d_model // h
    dt = x.dtype
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    xg = jnp.einsum("bd,dhge->bhge", xn[:, 0], p["w_g"].astype(dt)) + p["b_g"].astype(dt)
    rec = jnp.einsum("bhd,hgde->bhge", state["h"], p["r_w"].astype(jnp.float32))
    pre = xg.astype(jnp.float32) + rec
    il, fl, zl, ol = pre[:, :, 0], pre[:, :, 1], pre[:, :, 2], pre[:, :, 3]
    m_new = jnp.maximum(fl + state["m"], il)
    i_p = jnp.exp(il - m_new)
    f_p = jnp.exp(fl + state["m"] - m_new)
    c_new = f_p * state["c"] + i_p * jnp.tanh(zl)
    n_new = f_p * state["n"] + i_p
    h_new = jax.nn.sigmoid(ol) * c_new / jnp.maximum(n_new, 1.0)
    hs = rms_norm(h_new.reshape(b, cfg.d_model).astype(dt), p["ln_inner"], cfg.norm_eps)
    out = jax.nn.gelu(hs @ p["w_out"].astype(dt)) @ p["w_down"].astype(dt)
    new_state = {"c": c_new, "n": n_new, "m": m_new, "h": h_new}
    return x + out[:, None], new_state


# --------------------------------------------------------------------------
# whole-model decode step
# --------------------------------------------------------------------------
def _dense_layer_decode(lp, cfg, x, lcache, pos, impl, seq_max=None,
                        paged=None):
    h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
    if cfg.mla:
        attn, new_cache = mla_decode(lp["attn"], cfg, h, lcache, pos, impl,
                                     seq_max, paged)
    else:
        attn, new_cache = gqa_decode(lp["attn"], cfg, h, lcache, pos, impl,
                                     seq_max, paged)
    x = x + attn
    h = rms_norm(x, lp["norm_mlp"], cfg.norm_eps)
    if cfg.moe:
        ff, _ = moe_forward(lp["moe"], cfg, h)
    else:
        ff = mlp_forward(lp["mlp"], h, cfg.act)
    return x + ff, new_cache


def _hymba_layer_decode(lp, cfg, x, lcache, pos, impl, seq_max=None,
                        paged=None):
    h = rms_norm(x, lp["norm_mix"], cfg.norm_eps)
    attn, attn_cache = gqa_decode(lp["attn"], cfg, h, lcache["attn"], pos,
                                  impl, seq_max, paged)
    ssm, ssm_state = mamba_decode(lp["mamba"], cfg, h, lcache["mamba"])
    mixed = (
        lp["gate_attn"].astype(x.dtype) * attn + lp["gate_ssm"].astype(x.dtype) * ssm
    )
    x = x + mixed
    h = rms_norm(x, lp["norm_mlp"], cfg.norm_eps)
    x = x + mlp_forward(lp["mlp"], h, cfg.act)
    return x, {"attn": attn_cache, "mamba": ssm_state}


def decode_step(params, cfg: ModelConfig, cache: Cache, tokens: jnp.ndarray,
                seq_max: int | None = None, paged_table=None,
                paged_meta=None):
    """One decode step. tokens (B,1) int32. Returns (logits (B,1,V), cache).

    ``seq_max`` (optional) fixes the landmark segmentation horizon
    independently of the K/V view length — the paged engine gathers views
    only as long as the longest active sequence needs.

    ``paged_table`` ((n_slots,) int32, traced) + ``paged_meta``
    ((block_size, interpret), static) switch the gather-free paged route:
    seq-shaped cache leaves are the shared block pools (module docstring),
    and the returned cache carries each layer's NEW TOKEN in their place
    for ``PagedKVCache.make_paged_step`` to scatter-commit."""
    from repro.models.model import working_params

    paged = None if paged_table is None else (paged_table, *paged_meta)
    params = working_params(params, cfg)
    pos = cache["pos"]
    dt = jnp.dtype(cfg.compute_dtype)
    x = _embed_tokens(params, cfg, tokens).astype(dt)
    impl = cfg.decode_attention_impl

    if cfg.family == "ssm":
        new_layers = []
        for lp, lc in zip(params["layers"], cache["layers"]):
            if "kind_slstm" in lp:
                x, st = slstm_block_decode(lp["kind_slstm"], cfg, x, lc["kind_slstm"])
                new_layers.append({"kind_slstm": st})
            else:
                x, st = mlstm_block_decode(lp["kind_mlstm"], cfg, x, lc["kind_mlstm"])
                new_layers.append({"kind_mlstm": st})
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _unembed(params, cfg, x)
        return logits, {"pos": pos + 1, "layers": new_layers}

    if cfg.family == "audio":
        return _whisper_decode(params, cfg, cache, tokens, seq_max, paged)

    layer_decode = {
        "dense": _dense_layer_decode,
        "moe": _dense_layer_decode,
        "vlm": _dense_layer_decode,
        "hybrid": _hymba_layer_decode,
    }[cfg.family]

    if cfg.scan_layers and not isinstance(params["layers"], list):
        # Pool leaves scan fine: their layout keeps the layer axis leading
        # (the block pair replaced cache_seq in place), and each layer's
        # output carries only the new token, so the scan's stacked ys stay
        # O(L*c*d) — the pools are read-only xs.
        def body(y, xs):
            lp, lc = xs
            y, nc = layer_decode(lp, cfg, y, lc, pos, impl, seq_max, paged)
            return y, nc

        x, new_layer_cache = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
    else:
        new_list = []
        for lp, lc in zip(params["layers"], cache["layers"]):
            x, nc = layer_decode(lp, cfg, x, lc, pos, impl, seq_max, paged)
            new_list.append(nc)
        new_layer_cache = new_list

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layer_cache
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _whisper_decode(params, cfg: ModelConfig, cache, tokens, seq_max=None,
                    paged=None):
    pos = cache["pos"]
    dt = jnp.dtype(cfg.compute_dtype)
    x = _embed_tokens(params, cfg, tokens).astype(dt)
    x = x + jax.lax.dynamic_slice_in_dim(
        params["dec_pos"], jnp.minimum(pos, params["dec_pos"].shape[0] - 1), 1
    ).astype(dt)
    impl = cfg.decode_attention_impl
    new_layers = []
    for i, (lp, lc) in enumerate(zip(params["layers"], cache["layers"])):
        h = layer_norm(x, lp["ln_self"]["scale"], lp["ln_self"]["bias"], cfg.norm_eps)
        attn, nc = gqa_decode(lp["self_attn"], cfg, h, lc, pos, impl, seq_max,
                              paged)
        x = x + attn
        h = layer_norm(x, lp["ln_cross"]["scale"], lp["ln_cross"]["bias"], cfg.norm_eps)
        ck, cv = cache["cross_k"][i], cache["cross_v"][i]
        cp = lp["cross_attn"]
        q = jnp.einsum("bsd,dhe->bhse", h, cp["w_q"].astype(dt))
        scores = jnp.einsum(
            "bhqd,bhsd->bhqs", q.astype(jnp.float32), ck.astype(jnp.float32)
        ) * (cfg.resolved_head_dim**-0.5)
        pattn = jax.nn.softmax(scores, axis=-1)
        cr = jnp.einsum("bhqs,bhsd->bhqd", pattn, cv.astype(jnp.float32)).astype(dt)
        x = x + jnp.einsum("bhse,hed->bsd", cr, cp["w_o"].astype(dt))
        h = layer_norm(x, lp["ln_mlp"]["scale"], lp["ln_mlp"]["bias"], cfg.norm_eps)
        x = x + mlp_forward(lp["mlp"], h, "gelu")
        new_layers.append(nc)
    x = layer_norm(x, params["dec_ln"]["scale"], params["dec_ln"]["bias"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["pos"] = pos + 1
    return logits, new_cache
