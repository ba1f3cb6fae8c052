"""Batched prompt prefill: one jitted forward pass seeds the decode state.

The seed engine replayed prompts token-by-token through ``decode_step`` —
O(prompt_len) engine ticks (each a host round-trip) before the first
generated token. The spectral-shifting method makes whole-prompt prefill
cheap: the per-layer landmark state is just a fixed ``(c, d)`` running-sum
summary, so the entire prompt can be pushed through the model at once and
the cache seeded directly:

* K/V (or MLA latent/rope) for all prompt positions in one projection;
* ``q_lmk``/``k_lmk`` running sums as masked segment sums over the prompt
  (exactly what per-token ``_lmk_add`` would have accumulated);
* the streaming B-side decode state ``bv_m``/``bv_l``/``bv_acc``
  (serve/decode_state.py), seeded exactly for every reached landmark row:
  ``ss_fused`` streams the prompt through the ``landmark_summary`` kernel
  once and its online-softmax (m, l, BV) land directly in the cache;
  ``replay`` uses the jnp recompute. Decode then *appends* to this state
  instead of rebuilding B over the horizon each tick — and because
  scheduler preemption recomputes through this same prefill path on
  re-admission, a preempted request's streaming state is rebuilt exactly;
* per-position attention outputs, three ways (``prefill_impl``):
    - ``replay``  — the decode-path attention math vmapped over positions
      (per-position landmark prefixes), numerically equivalent to feeding
      tokens one at a time; honors ``cfg.decode_attention_impl``. MoE
      caveat: expert capacity is computed over the whole prompt here but
      per token in replay, so equivalence for moe families holds only in
      the dropless regime (large ``capacity_factor``);
    - ``ss_fused`` — the Pallas ``landmark_summary``/``query_side`` kernels
      (kernels/ss_attention.py) over the whole prompt: the O(n) streamed
      formulation, approximate for causal prompts (landmarks see the full
      prompt) but the cache it leaves behind is still exact.

Both modes right-pad prompts to a bucket multiple so only a handful of XLA
programs ever compile; all padded positions are masked out of cache writes
and landmark sums. In ``ss_fused`` mode the prompt length rides into the
kernels as a dynamic key-validity bound (``kv_valid``), so padded zero-keys
never enter the softmax normalization or the landmark means — the bucketed
program is numerically the unpadded one. The only exception is degenerate
prompts of <= num_landmarks tokens: they hit the exact-attention path, which
carries no key mask, so the engine slices them to exact length (tiny
programs, cheap recompiles; ``ss_attention_fused`` assert-guards padded
callers).

Supported for the attention-cache families (dense / moe / vlm, GQA or MLA).
Hybrid and SSM stacks keep token replay (their recurrent state is inherently
sequential); the engine falls back automatically.

Prefix caching (serve/paged.py) rides on the chunked variant of this path:
a partial hit attaches the shared blocks plus the dense snapshot captured
at the deepest block-aligned chunk boundary, then *resumes* chunked prefill
from that boundary — chunk starts are always block-aligned, so a resumed
prefill runs the exact same chunk programs a cold prefill would have run
from that offset, and the resulting cache is bitwise the cold one. A full
hit skips this module entirely (first-token logits come from the cache
entry).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import _broadcast_kv, ss_config_from
from repro.models.layers import apply_rotary, mlp_forward, rms_norm, rotary_angles
from repro.models.model import _embed_tokens, _unembed, working_params
from repro.models.moe import moe_forward
from repro.models.params import ParamSpec
from repro.runtime import interpret_kernels
from repro.serve.decode import (
    _segment_len,
    full_decode_attention,
    ss_decode_attention,
)
from repro.serve.decode_state import (
    STREAM_LEAVES,
    landmark_counts,
    landmark_means,
    mask_stats_rows,
    rebase_span,
    recompute_stats,
    segment_len,
)
from repro.serve.kv_cache import cache_specs


def prefill_supported(cfg: ModelConfig) -> bool:
    """Families whose whole decode state is derivable in one forward pass."""
    return cfg.family in ("dense", "moe", "vlm")


def _zero_cache(cfg: ModelConfig, seq_len: int) -> Any:
    specs = cache_specs(cfg, 1, seq_len)
    is_spec = lambda x: isinstance(x, ParamSpec)  # noqa: E731
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype or jnp.float32), specs,
        is_leaf=is_spec,
    )


def _routing(n: int, n_valid, seq_max: int, c: int):
    """Segment routing for a prompt window: (t_mask (n,), onehot (n, c))
    with positions >= n_valid zeroed out."""
    t = jnp.arange(n)
    t_mask = t < n_valid
    seg = t // _segment_len(seq_max, c)
    oh = jax.nn.one_hot(seg, c, dtype=jnp.float32) * t_mask[:, None]
    return t_mask, oh


def _prefix_sums(oh: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Per-position inclusive landmark prefix sums.

    oh (n, c) masked routing; x (B, H, n, d). Returns (n, B, H, c, d) where
    entry t equals the running sums ``_lmk_add`` would hold after feeding
    tokens 0..t — the state the decode path sees at position t."""
    contrib = oh[None, None, :, :, None] * x[:, :, :, None, :]  # (B,H,n,c,d)
    cum = jnp.cumsum(contrib.astype(jnp.float32), axis=2)
    return jnp.moveaxis(cum, 2, 0)


def _window_sums(oh: jnp.ndarray, x: jnp.ndarray, per_position: bool):
    """Landmark sums over the prompt window: per position (``_prefix_sums``,
    read by replay attention) or, when nothing reads the per-position
    prefixes, only the totals as a length-1 leading axis. The per-position
    tensor is n*H*c*d floats — 1.4 GB per layer at Qwen2-7B widths and
    n=1536, which ss_fused prefill would build only to keep its last row."""
    if per_position:
        return _prefix_sums(oh, x)
    return jnp.einsum(
        "nc,bhnd->bhcd", oh, x.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )[None]


def _attend_prefill(
    cfg: ModelConfig, impl: str, prefill_impl: str,
    q, k_b, v_b, q_sums, k_sums_b, scale, seq_max: int, t_mask,
    n_valid=None, block_n: int = 512, pos0=0,
):
    """Per-position attention over the prompt window.

    q (B,H,n,d); k_b/v_b kv-broadcast and pad-masked keys/values — prompt-
    window long for whole-prompt prefill, or an assembled prefix+chunk view
    (longer than n) for chunked prefill; q_sums/k_sums_b (n,B,H,c,d)
    landmark prefixes; ``n_valid`` the true prompt length (traced);
    ``pos0`` (traced) offsets query positions so a chunk window attends at
    its global positions. Returns (B,H,n,dv)."""
    n = q.shape[2]
    if prefill_impl == "ss_fused" and impl == "spectral_shift":
        from repro.core.attention import full_attention
        from repro.kernels.ops import ss_attention_fused

        if n <= cfg.num_landmarks:
            # Degenerate window: this is the exact-attention regime the
            # unpadded call would hit (n <= c), computed here with the
            # key-validity mask applied directly so a bucket-padded tiny
            # prompt stays exact too (the fused degenerate path carries no
            # mask; the window is <= c tokens, so O(n^2) is trivial).
            key_mask = (jnp.arange(n) < n_valid)[None, None, None, :]
            return full_attention(q, k_b, v_b, mask=key_mask, scale=scale)
        # Bucketed padding: kv_valid masks padded zero-keys out of the
        # softmax normalization and the landmark means, so this computes
        # exactly what the unpadded call would. Contract: n_valid must
        # exceed num_landmarks here (the engine slices shorter prompts to
        # exact-length windows, taking the branch above).
        return ss_attention_fused(
            q, k_b, v_b, ss_config_from(cfg, causal=False), scale=scale,
            interpret=interpret_kernels(), block_n=block_n,
            kv_valid=n_valid,
        )
    qs = jnp.moveaxis(q, 2, 0)[:, :, :, None, :]  # (n, B, H, 1, d)
    pos_t = pos0 + jnp.arange(n)
    if impl == "spectral_shift":
        def one(qt, qsum, ksum, pos):
            return ss_decode_attention(
                qt, k_b, v_b, qsum, ksum, pos, cfg, scale, seq_max=seq_max
            )
    else:
        def one(qt, qsum, ksum, pos):
            return full_decode_attention(qt, k_b, v_b, pos, scale)

    outs = jax.vmap(one)(qs, q_sums, k_sums_b, pos_t)  # (n, B, H, 1, dv)
    return jnp.moveaxis(outs[:, :, :, 0, :], 0, 2)      # (B, H, n, dv)


def _seed_stream_stats(cfg: ModelConfig, prefill_impl: str, q_l, kb, vb,
                       n_valid, scale, seq_max: int, block_n: int):
    """Streaming decode state (serve/decode_state.py) for one layer, seeded
    in one shot from the whole prompt: per-landmark online-softmax partials
    (m, l, acc) over keys 0..n_valid-1, keyed by the cache's horizon-
    segmented landmark means ``q_l`` (B, H, c, d).

    ``ss_fused`` prefill streams the prompt through the ``landmark_summary``
    Pallas kernel once (kv_valid-masked, so bucket padding stays invisible)
    and hands the kernel's (m, l, BV) directly into the cache — the
    prefill->decode handoff costs one O(n) kernel pass. Other modes (replay,
    degenerate <= c windows) use the jnp ``recompute_stats``. Rows past the
    active segment are zeroed (the streaming invariant)."""
    c = cfg.num_landmarks
    pos_last = n_valid - 1
    if cfg.decode_attention_impl != "spectral_shift":
        z = jnp.zeros((*q_l.shape[:3], 1), jnp.float32)
        return z, z, jnp.zeros((*q_l.shape[:3], vb.shape[-1]), jnp.float32)
    if prefill_impl == "ss_fused" and kb.shape[2] > c:
        from repro.kernels.ss_attention import landmark_summary

        b, h, n, d = kb.shape
        dv = vb.shape[-1]
        bv, m, l = landmark_summary(
            q_l.reshape(b * h, c, d),
            kb.reshape(b * h, n, d),
            vb.reshape(b * h, n, dv),
            scale=scale, block_n=block_n, interpret=interpret_kernels(),
            return_stats=True, kv_valid=n_valid,
        )
        m = m.reshape(b, h, c, 1)
        l = l.reshape(b, h, c, 1)
        acc = bv.astype(jnp.float32).reshape(b, h, c, dv) * l
    else:
        m, l, acc = recompute_stats(q_l, kb, vb, pos_last, scale)
    keep = jnp.arange(c) <= pos_last // segment_len(seq_max, c)
    return mask_stats_rows((m, l, acc), keep)


# --------------------------------------------------------------------------
# per-layer prefill (mirrors gqa_decode / mla_decode, vectorized over n)
# --------------------------------------------------------------------------
def _gqa_prefill(p, cfg: ModelConfig, x, sin, cos, t_mask, oh, seq_max, impl,
                 prefill_impl, n_valid, block_n):
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bhse", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhe->bhse", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhe->bhse", x, p["w_v"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(dt)[None, :, None, :]
        k = k + p["b_k"].astype(dt)[None, :, None, :]
        v = v + p["b_v"].astype(dt)[None, :, None, :]
    if cfg.rope_theta > 0:
        q = apply_rotary(q, sin, cos)
        k = apply_rotary(k, sin, cos)

    pad = t_mask[None, None, :, None]
    k_m = jnp.where(pad, k, 0).astype(k.dtype)
    v_m = jnp.where(pad, v, 0).astype(v.dtype)

    # ss_fused attention reads no per-position landmark prefixes
    per_pos = not (prefill_impl == "ss_fused" and impl == "spectral_shift")
    q_sums = _window_sums(oh, q, per_pos)      # (n|1, B, H, c, d)
    k_sums = _window_sums(oh, k_m, per_pos)    # (n|1, B, Hkv, c, d)
    kb = _broadcast_kv(k_m, cfg.num_heads)
    vb = _broadcast_kv(v_m, cfg.num_heads)
    k_sums_b = jax.vmap(_broadcast_kv, (0, None))(k_sums, cfg.num_heads)

    scale = cfg.resolved_head_dim ** -0.5
    out = _attend_prefill(
        cfg, impl, prefill_impl, q, kb, vb, q_sums, k_sums_b,
        scale, seq_max, t_mask, n_valid, block_n,
    )
    c = cfg.num_landmarks
    counts = landmark_counts(n_valid - 1, seq_max, c)
    bv_m, bv_l, bv_acc = _seed_stream_stats(
        cfg, prefill_impl, landmark_means(q_sums[-1], counts), kb, vb,
        n_valid, scale, seq_max, block_n,
    )
    new_cache = {
        "k": k_m, "v": v_m,
        "q_lmk": q_sums[-1].astype(jnp.float32),
        "k_lmk": k_sums[-1].astype(jnp.float32),
        "bv_m": bv_m, "bv_l": bv_l, "bv_acc": bv_acc,
    }
    attn = jnp.einsum("bhse,hed->bsd", out.astype(dt), p["w_o"].astype(dt))
    return attn, new_cache


def _mla_prefill(p, cfg: ModelConfig, x, sin, cos, t_mask, oh, seq_max, impl,
                 prefill_impl, n_valid, block_n):
    dt = x.dtype
    dh, dr = cfg.resolved_head_dim, cfg.rope_head_dim
    c_kv = rms_norm(x @ p["w_dkv"].astype(dt), p["norm_kv"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,de->bse", x, p["w_k_rope"].astype(dt))[:, None]
    k_rope = apply_rotary(k_rope, sin, cos)[:, 0]  # (B, n, dr)

    q_nope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_nope"].astype(dt))
    q_rope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_rope"].astype(dt))
    q_rope = apply_rotary(q_rope, sin, cos)
    q_abs = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)  # (B, H, n, r+dr)

    pad2 = t_mask[None, :, None]
    c_kv_m = jnp.where(pad2, c_kv, 0).astype(c_kv.dtype)
    k_rope_m = jnp.where(pad2, k_rope, 0).astype(k_rope.dtype)
    k_eff = jnp.concatenate([c_kv_m, k_rope_m], axis=-1)  # (B, n, r+dr)

    per_pos = not (prefill_impl == "ss_fused" and impl == "spectral_shift")
    q_sums = _window_sums(oh, q_eff, per_pos)                    # (n|1,B,H,c,de)
    k_sums = _window_sums(oh, k_eff[:, None], per_pos)[:, :, 0]  # (n|1,B,c,de)

    h = cfg.num_heads
    k_eff_b = jnp.broadcast_to(
        k_eff[:, None], (k_eff.shape[0], h, *k_eff.shape[1:])
    )
    lat_b = jnp.broadcast_to(
        c_kv_m[:, None], (c_kv_m.shape[0], h, *c_kv_m.shape[1:])
    )
    k_sums_b = jnp.broadcast_to(
        k_sums[:, :, None], (*k_sums.shape[:2], h, *k_sums.shape[2:])
    )
    scale = (dh + dr) ** -0.5
    out_lat = _attend_prefill(
        cfg, impl, prefill_impl, q_eff, k_eff_b, lat_b, q_sums, k_sums_b,
        scale, seq_max, t_mask, n_valid, block_n,
    )
    out = jnp.einsum("bhsr,rhe->bhse", out_lat.astype(dt), p["w_uv"].astype(dt))
    attn = jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(dt))
    c = cfg.num_landmarks
    counts = landmark_counts(n_valid - 1, seq_max, c)
    bv_m, bv_l, bv_acc = _seed_stream_stats(
        cfg, prefill_impl, landmark_means(q_sums[-1], counts), k_eff_b,
        lat_b, n_valid, scale, seq_max, block_n,
    )
    new_cache = {
        "latent": c_kv_m, "rope": k_rope_m,
        "q_lmk": q_sums[-1].astype(jnp.float32),
        "k_lmk": k_sums[-1].astype(jnp.float32),
        "bv_m": bv_m, "bv_l": bv_l, "bv_acc": bv_acc,
    }
    return attn, new_cache


def _dense_layer_prefill(lp, cfg: ModelConfig, x, sin, cos, t_mask, oh,
                         seq_max, impl, prefill_impl, n_valid, block_n):
    h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
    fn = _mla_prefill if cfg.mla else _gqa_prefill
    attn, new_cache = fn(
        lp["attn"], cfg, h, sin, cos, t_mask, oh, seq_max, impl, prefill_impl,
        n_valid, block_n,
    )
    x = x + attn
    h = rms_norm(x, lp["norm_mlp"], cfg.norm_eps)
    if cfg.moe:
        ff, _ = moe_forward(lp["moe"], cfg, h)
    else:
        ff = mlp_forward(lp["mlp"], h, cfg.act)
    return x + ff, new_cache


# --------------------------------------------------------------------------
# whole-prompt prefill
# --------------------------------------------------------------------------
def batched_prefill(
    params, cfg: ModelConfig, tokens: jnp.ndarray, n_valid: jnp.ndarray,
    *, seq_max: int, prefill_impl: str = "replay", block_n: int = 512,
):
    """Run a whole (padded) prompt through the model in one pass.

    tokens (1, n_pad) int32, n_valid scalar int32 <= n_pad. Returns
    ``(logits (1, n_pad, V), cache)`` where ``cache`` matches
    ``cache_specs(cfg, 1, n_pad)`` in structure: K/V filled for positions
    < n_valid (zeros elsewhere), landmark running sums accumulated over the
    first n_valid tokens with ``seq_max`` segment routing, pos = n_valid.
    The next-token logits live at index ``n_valid - 1``.

    ``prefill_impl="ss_fused"`` contract: when the padded window exceeds
    ``cfg.num_landmarks``, ``n_valid`` must too — the masked kernels model
    the unpadded >c regime, while a <=c prompt belongs on the exact path
    (the engine slices such prompts to windows <= num_landmarks, where the
    masked exact branch handles any ``n_valid``).
    """
    if not prefill_supported(cfg):
        raise ValueError(f"batched prefill unsupported for family {cfg.family}")
    if (prefill_impl == "ss_fused"
            and tokens.shape[1] > cfg.num_landmarks
            and not isinstance(n_valid, jax.core.Tracer)
            and int(n_valid) <= cfg.num_landmarks):
        # Concrete (eager) callers get the contract enforced loudly; under
        # jit n_valid is a tracer and the engine's window slicing upholds it.
        raise ValueError(
            f"ss_fused prefill: prompt length {int(n_valid)} <= "
            f"num_landmarks {cfg.num_landmarks} must run in a window of at "
            f"most num_landmarks tokens (the engine slices such prompts) — "
            f"the masked kernels model the > num_landmarks regime only"
        )
    params = working_params(params, cfg)
    cache = _zero_cache(cfg, tokens.shape[1])
    dt = jnp.dtype(cfg.compute_dtype)
    n = tokens.shape[1]
    x = _embed_tokens(params, cfg, tokens).astype(dt)
    impl = cfg.decode_attention_impl

    c = cfg.num_landmarks
    t_mask, oh = _routing(n, n_valid, seq_max, c)
    positions = jnp.arange(n)[None]  # (1, n)
    rope_dim = cfg.rope_head_dim if cfg.mla else cfg.resolved_head_dim
    sin, cos = rotary_angles(positions, rope_dim, cfg.rope_theta)
    sin, cos = sin[:, None], cos[:, None]  # (1, 1, n, dh/2)

    layer_fn = functools.partial(
        _dense_layer_prefill, cfg=cfg, sin=sin, cos=cos, t_mask=t_mask,
        oh=oh, seq_max=seq_max, impl=impl, prefill_impl=prefill_impl,
        n_valid=jnp.asarray(n_valid, jnp.int32), block_n=block_n,
    )
    if cfg.scan_layers and not isinstance(params["layers"], list):
        def body(y, lp):
            y, nc = layer_fn(lp, x=y)
            return y, nc

        x, new_layers = jax.lax.scan(body, x, params["layers"])
    else:
        new_layers = []
        for lp in params["layers"]:
            x, nc = layer_fn(lp, x=x)
            new_layers.append(nc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["pos"] = jnp.asarray(n_valid, jnp.int32)
    return logits, new_cache


# --------------------------------------------------------------------------
# chunked prefill (continuous batching): one fixed-size prompt chunk per
# call, carrying the landmark state across chunks
# --------------------------------------------------------------------------
def _insert_chunk(view, chunk, start, axis: int):
    """Extend a committed-prefix cache view (seq ``axis``) by one chunk:
    pad the view by the chunk length (so a tail chunk can never clamp the
    dynamic write backwards into committed data), then write the chunk's
    rows at global position ``start``."""
    n = chunk.shape[axis]
    pad = [(0, 0)] * view.ndim
    pad[axis] = (0, n)
    ext = jnp.pad(view.astype(chunk.dtype), pad)
    idx = [0] * view.ndim
    idx[axis] = start
    return jax.lax.dynamic_update_slice(ext, chunk, tuple(idx))


def _merge_chunk_stats(cfg: ModelConfig, stats_impl: str, carry, q_l, kb, vb,
                       k_full_b, v_full_b, start, chunk_valid, scale,
                       seq_max: int, block_n: int):
    """Streaming-stat carry across prefill chunks for one layer.

    ``carry`` = the lane's (bv_m, bv_l, bv_acc) leaves after the previous
    chunk (the ``_seed_stream_stats`` state for prompt length ``start``);
    ``q_l`` the landmark means at ``end = start + chunk_valid``; kb/vb the
    chunk window's keys/values (head-broadcast, pad-masked); k_full_b /
    v_full_b the assembled keys 0..end-1. Returns the state whole-prompt
    seeding would produce for prompt length ``end`` (frozen rows up to
    softmax reassociation):

    * rows frozen before the chunk (r < start//seg — their landmark means
      were already final) take the chunk window's partial, computed with
      those final means, merged into the carry via ``flash_merge`` — the
      ss_fused handoff streams the window through ``landmark_summary``;
    * rows whose mean moved (or that were founded) inside the chunk —
      the contiguous span start//seg..(end-1)//seg — are recomputed
      exactly over the assembled view (``rebase_span``);
    * rows past the active segment stay zero (the streaming invariant)."""
    c = cfg.num_landmarks
    if cfg.decode_attention_impl != "spectral_shift":
        return tuple(jnp.zeros_like(s, jnp.float32) for s in carry)
    seg = segment_len(seq_max, c)
    chunk_pad = kb.shape[2]
    end_pos = start + chunk_valid - 1
    if stats_impl == "ss_fused" and chunk_pad > c:
        from repro.kernels.ss_attention import landmark_summary

        b, h, n, d = kb.shape
        dv = vb.shape[-1]
        bv, m_w, l_w = landmark_summary(
            q_l.reshape(b * h, c, d),
            kb.reshape(b * h, n, d),
            vb.reshape(b * h, n, dv),
            scale=scale, block_n=block_n, interpret=interpret_kernels(),
            return_stats=True, kv_valid=chunk_valid,
        )
        m_w = m_w.reshape(b, h, c, 1)
        l_w = l_w.reshape(b, h, c, 1)
        acc_w = bv.astype(jnp.float32).reshape(b, h, c, dv) * l_w
    else:
        m_w, l_w, acc_w = recompute_stats(q_l, kb, vb, chunk_valid - 1, scale)
    from repro.kernels.ops import flash_merge

    carry32 = tuple(s.astype(jnp.float32) for s in carry)
    m_f, l_f, acc_f = flash_merge(*carry32, m_w, l_w, acc_w)
    frozen = (jnp.arange(c) < start // seg)[:, None]
    m = jnp.where(frozen, m_f, carry32[0])
    l = jnp.where(frozen, l_f, carry32[1])
    acc = jnp.where(frozen, acc_f, carry32[2])
    row_lo = start // seg
    row_hi = end_pos // seg
    span = min(chunk_pad // seg + 2, c)
    m, l, acc = rebase_span(
        (m, l, acc), q_l, k_full_b, v_full_b, end_pos, scale,
        row_lo, row_hi, span,
    )
    keep = jnp.arange(c) <= row_hi
    return mask_stats_rows((m, l, acc), keep)


def _gqa_chunk(p, cfg: ModelConfig, x, sin, cos, t_mask, oh, seq_max, impl,
               stats_impl, start, chunk_valid, lcache, block_n):
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bhse", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhe->bhse", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhe->bhse", x, p["w_v"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(dt)[None, :, None, :]
        k = k + p["b_k"].astype(dt)[None, :, None, :]
        v = v + p["b_v"].astype(dt)[None, :, None, :]
    if cfg.rope_theta > 0:
        q = apply_rotary(q, sin, cos)
        k = apply_rotary(k, sin, cos)

    pad = t_mask[None, None, :, None]
    k_m = jnp.where(pad, k, 0).astype(k.dtype)
    v_m = jnp.where(pad, v, 0).astype(v.dtype)

    # landmark prefixes continue the lane's running sums
    q_sums = lcache["q_lmk"][None] + _prefix_sums(oh, q)
    k_sums = lcache["k_lmk"][None] + _prefix_sums(oh, k_m)
    kb = _broadcast_kv(k_m, cfg.num_heads)
    vb = _broadcast_kv(v_m, cfg.num_heads)
    k_sums_b = jax.vmap(_broadcast_kv, (0, None))(k_sums, cfg.num_heads)

    # assembled keys 0..end-1: committed view + this chunk at [start, end)
    k_full = _insert_chunk(lcache["k"], k_m, start, axis=2)
    v_full = _insert_chunk(lcache["v"], v_m, start, axis=2)
    kfb = _broadcast_kv(k_full, cfg.num_heads)
    vfb = _broadcast_kv(v_full, cfg.num_heads)

    scale = cfg.resolved_head_dim ** -0.5
    out = _attend_prefill(
        cfg, impl, "replay", q, kfb, vfb, q_sums, k_sums_b,
        scale, seq_max, t_mask, chunk_valid, block_n, pos0=start,
    )
    c = cfg.num_landmarks
    counts = landmark_counts(start + chunk_valid - 1, seq_max, c)
    q_l = landmark_means(q_sums[-1], counts)
    bv_m, bv_l, bv_acc = _merge_chunk_stats(
        cfg, stats_impl, tuple(lcache[nm] for nm in STREAM_LEAVES),
        q_l, kb, vb, kfb, vfb, start, chunk_valid, scale, seq_max, block_n,
    )
    new_cache = {
        "k": k_m, "v": v_m,
        "q_lmk": q_sums[-1].astype(jnp.float32),
        "k_lmk": k_sums[-1].astype(jnp.float32),
        "bv_m": bv_m, "bv_l": bv_l, "bv_acc": bv_acc,
    }
    attn = jnp.einsum("bhse,hed->bsd", out.astype(dt), p["w_o"].astype(dt))
    return attn, new_cache


def _mla_chunk(p, cfg: ModelConfig, x, sin, cos, t_mask, oh, seq_max, impl,
               stats_impl, start, chunk_valid, lcache, block_n):
    dt = x.dtype
    dh, dr = cfg.resolved_head_dim, cfg.rope_head_dim
    c_kv = rms_norm(x @ p["w_dkv"].astype(dt), p["norm_kv"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,de->bse", x, p["w_k_rope"].astype(dt))[:, None]
    k_rope = apply_rotary(k_rope, sin, cos)[:, 0]  # (B, n, dr)

    q_nope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_nope"].astype(dt))
    q_rope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_rope"].astype(dt))
    q_rope = apply_rotary(q_rope, sin, cos)
    q_abs = jnp.einsum("bhse,rhe->bhsr", q_nope, p["w_uk"].astype(dt))
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)

    pad2 = t_mask[None, :, None]
    c_kv_m = jnp.where(pad2, c_kv, 0).astype(c_kv.dtype)
    k_rope_m = jnp.where(pad2, k_rope, 0).astype(k_rope.dtype)
    k_eff = jnp.concatenate([c_kv_m, k_rope_m], axis=-1)

    q_sums = lcache["q_lmk"][None] + _prefix_sums(oh, q_eff)
    k_sums = (
        lcache["k_lmk"][None] + _prefix_sums(oh, k_eff[:, None])[:, :, 0]
    )

    h = cfg.num_heads
    k_eff_b = jnp.broadcast_to(
        k_eff[:, None], (k_eff.shape[0], h, *k_eff.shape[1:])
    )
    lat_b = jnp.broadcast_to(
        c_kv_m[:, None], (c_kv_m.shape[0], h, *c_kv_m.shape[1:])
    )
    lat_full = _insert_chunk(lcache["latent"], c_kv_m, start, axis=1)
    rope_full = _insert_chunk(lcache["rope"], k_rope_m, start, axis=1)
    k_eff_full = jnp.concatenate([lat_full, rope_full], axis=-1)
    kfb = jnp.broadcast_to(
        k_eff_full[:, None], (k_eff_full.shape[0], h, *k_eff_full.shape[1:])
    )
    vfb = jnp.broadcast_to(
        lat_full[:, None], (lat_full.shape[0], h, *lat_full.shape[1:])
    )
    k_sums_b = jnp.broadcast_to(
        k_sums[:, :, None], (*k_sums.shape[:2], h, *k_sums.shape[2:])
    )
    scale = (dh + dr) ** -0.5
    out_lat = _attend_prefill(
        cfg, impl, "replay", q_eff, kfb, vfb, q_sums, k_sums_b,
        scale, seq_max, t_mask, chunk_valid, block_n, pos0=start,
    )
    out = jnp.einsum("bhsr,rhe->bhse", out_lat.astype(dt), p["w_uv"].astype(dt))
    attn = jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(dt))
    counts = landmark_counts(
        start + chunk_valid - 1, seq_max, cfg.num_landmarks
    )
    q_l = landmark_means(q_sums[-1], counts)
    bv_m, bv_l, bv_acc = _merge_chunk_stats(
        cfg, stats_impl, tuple(lcache[nm] for nm in STREAM_LEAVES),
        q_l, k_eff_b, lat_b, kfb, vfb, start, chunk_valid, scale, seq_max,
        block_n,
    )
    new_cache = {
        "latent": c_kv_m, "rope": k_rope_m,
        "q_lmk": q_sums[-1].astype(jnp.float32),
        "k_lmk": k_sums[-1].astype(jnp.float32),
        "bv_m": bv_m, "bv_l": bv_l, "bv_acc": bv_acc,
    }
    return attn, new_cache


def _dense_layer_chunk(lp, lc, cfg: ModelConfig, x, sin, cos, t_mask, oh,
                       seq_max, impl, stats_impl, start, chunk_valid,
                       block_n):
    h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
    fn = _mla_chunk if cfg.mla else _gqa_chunk
    attn, new_cache = fn(
        lp["attn"], cfg, h, sin, cos, t_mask, oh, seq_max, impl, stats_impl,
        start, chunk_valid, lc, block_n,
    )
    x = x + attn
    h = rms_norm(x, lp["norm_mlp"], cfg.norm_eps)
    if cfg.moe:
        ff, _ = moe_forward(lp["moe"], cfg, h)
    else:
        ff = mlp_forward(lp["mlp"], h, cfg.act)
    return x + ff, new_cache


def chunk_prefill(
    params, cfg: ModelConfig, cache: Any, tokens: jnp.ndarray, start,
    chunk_valid, *, seq_max: int, stats_impl: str = "replay",
    block_n: int = 512,
):
    """Advance a mid-prefill lane by one fixed-size prompt chunk.

    ``cache`` is the lane's B=1 assembled view (committed K/V for positions
    < ``start``, plus the dense landmark/stream leaves carried from the
    previous chunk); ``tokens`` (1, chunk_pad) the chunk window with
    ``chunk_valid`` real tokens at global positions start..start+valid-1
    (``start``/``chunk_valid`` traced). Returns ``(logits (1, chunk_pad, V),
    new_cache)`` where seq leaves hold the CHUNK's K/V only (the caller
    commits them at the chunk's blocks) and dense leaves the carried-forward
    state; last-token logits live at ``chunk_valid - 1``.

    Chunk attention is the exact per-position replay math at global
    positions over the assembled view — token-identical to feeding the
    prompt one token at a time, hence to whole-prompt ``replay`` prefill.
    ``stats_impl`` only routes the streaming-stat window handoff
    (``_merge_chunk_stats``): ``ss_fused`` streams each chunk window through
    the ``landmark_summary`` kernel, ``replay`` uses the jnp recompute; the
    resulting cache is the same up to softmax reassociation. (Whole-prompt
    ``ss_fused`` *attention* is non-causal over the prompt and so cannot be
    chunked; chunked mode upgrades it to the exact outputs instead.) MoE
    caveat as whole-prompt: expert capacity is computed per chunk window,
    so replay equivalence holds in the dropless regime."""
    if not prefill_supported(cfg):
        raise ValueError(f"chunked prefill unsupported for family {cfg.family}")
    params = working_params(params, cfg)
    dt = jnp.dtype(cfg.compute_dtype)
    n = tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    chunk_valid = jnp.asarray(chunk_valid, jnp.int32)
    x = _embed_tokens(params, cfg, tokens).astype(dt)
    impl = cfg.decode_attention_impl

    c = cfg.num_landmarks
    t = jnp.arange(n)
    t_mask = t < chunk_valid
    seg_idx = (start + t) // _segment_len(seq_max, c)
    oh = jax.nn.one_hot(seg_idx, c, dtype=jnp.float32) * t_mask[:, None]
    positions = (start + t)[None]  # (1, n) global positions
    rope_dim = cfg.rope_head_dim if cfg.mla else cfg.resolved_head_dim
    sin, cos = rotary_angles(positions, rope_dim, cfg.rope_theta)
    sin, cos = sin[:, None], cos[:, None]

    layer_fn = functools.partial(
        _dense_layer_chunk, cfg=cfg, sin=sin, cos=cos, t_mask=t_mask, oh=oh,
        seq_max=seq_max, impl=impl, stats_impl=stats_impl, start=start,
        chunk_valid=chunk_valid, block_n=block_n,
    )
    if cfg.scan_layers and not isinstance(params["layers"], list):
        def body(y, lp_lc):
            lp, lc = lp_lc
            y, nc = layer_fn(lp, lc, x=y)
            return y, nc

        x, new_layers = jax.lax.scan(
            body, x, (params["layers"], cache["layers"])
        )
    else:
        new_layers = []
        for lp, lc in zip(params["layers"], cache["layers"]):
            x, nc = layer_fn(lp, lc, x=x)
            new_layers.append(nc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    new_cache = dict(_zero_cache(cfg, n))
    new_cache["layers"] = new_layers
    new_cache["pos"] = jnp.asarray(start + chunk_valid, jnp.int32)
    return logits, new_cache


def make_chunk_prefill_fn(cfg: ModelConfig, *, seq_max: int,
                          stats_impl: str = "replay", block_n: int = 512):
    """Chunk-prefill function ``fn(params, cache, tokens, start,
    chunk_valid)`` for ``PagedKVCache.make_chunk_step`` (which jits the
    fused gather -> chunk -> commit program; one XLA program per bucketed
    view length)."""
    def fn(params, cache, tokens, start, chunk_valid):
        return chunk_prefill(
            params, cfg, cache, tokens, start, chunk_valid,
            seq_max=seq_max, stats_impl=stats_impl, block_n=block_n,
        )

    return fn


def make_prefill_fn(params, cfg: ModelConfig, *, seq_max: int,
                    prefill_impl: str = "replay", block_n: int = 512):
    """Prefill callable ``fn(tokens, n_valid)``; jax.jit specializes one
    XLA program per padded prompt length — per bucket in both modes
    (``ss_fused`` masks the pad via ``kv_valid``), plus one exact-length
    program per degenerate <= num_landmarks prompt in ``ss_fused`` mode.
    ``block_n`` is the Pallas stream block (dispatch plan for the serve
    shape). ``params`` enter the program as an argument, never as captured
    constants (XLA would embed every weight in the program)."""
    def prefill(p, tokens, n_valid):  # jit(prefill) on a device trace
        return batched_prefill(
            p, cfg, tokens, n_valid, seq_max=seq_max,
            prefill_impl=prefill_impl, block_n=block_n,
        )

    jitted = jax.jit(prefill)

    def call(tokens, n_valid):
        return jitted(params, tokens, n_valid)

    call._jitted = jitted  # jit-cache probe for telemetry/accounting.py
    return call
