"""Model-level attention layers: GQA (with optional QKV bias) and MLA
(DeepSeek-V2 latent attention), wired to the paper's spectral-shifting
approximation through ``repro.core``.

Conventions
-----------
* hidden states: (B, S, D); per-head tensors: (B, H, S, Dh).
* ``mode``: "causal" (decoder train/prefill), "bidir" (encoder sites),
  "decode" (single step against a KV cache dict).
* GQA KV heads are broadcast to the query-head count before the core
  attention call; under TP the query heads are sharded over "model" and the
  broadcast stays local (no collective).
* Decode caches carry landmark *sums* so spectral-shift decode needs no
  O(n) landmark recomputation per token (counts are derived from ``pos``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import (
    SSConfig,
    chunked_attention,
    full_attention,
    spectral_shift_attention,
)
from repro.core.landmarks import segment_means
from repro.models.layers import apply_rotary, rotary_angles
from repro.models.params import ParamSpec


def ss_config_from(cfg: ModelConfig, causal: bool = False) -> SSConfig:
    return SSConfig(
        num_landmarks=cfg.num_landmarks,
        pinv_iters=cfg.pinv_iters,
        method=cfg.ss_method,
        include_shift_identity=cfg.include_shift_identity,
        causal=causal,
        landmark_via_matmul=cfg.landmark_via_matmul,
    )


def _core_attention(cfg: ModelConfig, impl: str, q, k, v, *, causal: bool):
    """q (B,H,S,Dh) vs k/v (B,H,S,Dh) -> (B,H,S,Dh)."""
    if impl == "full":
        return full_attention(q, k, v, causal=causal)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal,
                                 unroll=cfg.unroll_scans)
    if impl == "spectral_shift_fused":
        # Pallas-kernel-backed path, routed through the dispatch registry
        # (kernels/dispatch.py): plan = impl + block size per shape key,
        # resolved at trace time. Both the bidirectional and the
        # segment-causal variant run fused; grads flow through the
        # custom-VJP backward kernels. When the active sharding rules map
        # the sequence axis onto >1 devices, dispatch routes through the
        # shard_map context-parallel driver (kernels/sharded.py) — the key
        # carries seq_shards, so context-parallel cells keep the fused path.
        from repro.kernels.dispatch import dispatch_ss_attention
        from repro.runtime import interpret_kernels

        return dispatch_ss_attention(
            q, k, v, ss_config_from(cfg, causal=causal),
            backend=cfg.attention_backend,
            autotune_enabled=cfg.autotune,
            interpret=interpret_kernels(),
        )
    if impl in ("spectral_shift", "nystrom"):
        ss = ss_config_from(cfg, causal=causal)
        if impl == "nystrom":
            ss = SSConfig(
                num_landmarks=ss.num_landmarks, pinv_iters=ss.pinv_iters,
                method=ss.method, use_shift=False,
                include_shift_identity=False, causal=causal,
            )
        return spectral_shift_attention(q, k, v, ss)
    raise ValueError(f"unknown attention impl {impl!r}")


def _broadcast_kv(x: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """(B, Hkv, S, Dh) -> (B, H, S, Dh) by group broadcast."""
    b, hkv, s, d = x.shape
    if hkv == num_heads:
        return x
    g = num_heads // hkv
    x = jnp.broadcast_to(x[:, :, None], (b, hkv, g, s, d))
    return x.reshape(b, num_heads, s, d)


# ==========================================================================
# GQA attention
# ==========================================================================
def gqa_specs(cfg: ModelConfig) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    specs = {
        "w_q": ParamSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "w_k": ParamSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "w_v": ParamSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "w_o": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs.update(
            b_q=ParamSpec((h, dh), ("heads", "head_dim"), init="zeros"),
            b_k=ParamSpec((hkv, dh), ("kv_heads", "head_dim"), init="zeros"),
            b_v=ParamSpec((hkv, dh), ("kv_heads", "head_dim"), init="zeros"),
        )
    return specs


def gqa_project_qkv(p: dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    """x (B,S,D) -> q (B,H,S,Dh), k/v (B,Hkv,S,Dh), rotary applied."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bhse", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhe->bhse", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhe->bhse", x, p["w_v"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(dt)[None, :, None, :]
        k = k + p["b_k"].astype(dt)[None, :, None, :]
        v = v + p["b_v"].astype(dt)[None, :, None, :]
    if cfg.rope_theta > 0:
        sin, cos = rotary_angles(positions, cfg.resolved_head_dim, cfg.rope_theta)
        sin, cos = sin[:, None], cos[:, None]  # (B,1,S,Dh/2)
        q, k = apply_rotary(q, sin, cos), apply_rotary(k, sin, cos)
    return q, k, v


def gqa_forward(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    impl: str,
    mode: str = "causal",
    cache: Optional[dict] = None,
) -> tuple[jnp.ndarray, Optional[dict]]:
    """Full-sequence GQA attention; ``decode`` mode handled in serve/decode.py."""
    q, k, v = gqa_project_qkv(p, cfg, x, positions)
    k = _broadcast_kv(k, cfg.num_heads)
    v = _broadcast_kv(v, cfg.num_heads)
    out = _core_attention(cfg, impl, q, k, v, causal=(mode == "causal"))
    out = jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(x.dtype))
    return out, cache


def cross_attention_specs(cfg: ModelConfig) -> dict:
    return gqa_specs(cfg)


def cross_attention_forward(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    enc_out: jnp.ndarray,
    *,
    impl: str,
) -> jnp.ndarray:
    """Decoder-side cross attention over encoder output (no rotary, bidir)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bhse", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dhe->bhse", enc_out.astype(dt), p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dhe->bhse", enc_out.astype(dt), p["w_v"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(dt)[None, :, None, :]
        k = k + p["b_k"].astype(dt)[None, :, None, :]
        v = v + p["b_v"].astype(dt)[None, :, None, :]
    k = _broadcast_kv(k, cfg.num_heads)
    v = _broadcast_kv(v, cfg.num_heads)
    if (impl in ("spectral_shift", "spectral_shift_fused", "nystrom")
            and x.shape[1] != enc_out.shape[1]):
        # Cross attention with n_q != n_k: landmark counts must match; take
        # both landmark sets from their own sequences. The rectangular score
        # matrix has no diagonal, so the + delta*I output term is disabled
        # (the decode-convention branch in spectral_shift_attention is for
        # suffix queries of the SAME sequence, not cross attention).
        import dataclasses as _dc

        ss = _dc.replace(ss_config_from(cfg), include_shift_identity=False)
        q_l = segment_means(q, ss.num_landmarks, via_matmul=ss.landmark_via_matmul)
        k_l = segment_means(k, ss.num_landmarks, via_matmul=ss.landmark_via_matmul)
        out = spectral_shift_attention(q, k, v, ss, q_landmarks=q_l, k_landmarks=k_l)
    else:
        out = _core_attention(cfg, impl, q, k, v, causal=False)
    return jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(dt))


# ==========================================================================
# MLA — Multi-head Latent Attention (DeepSeek-V2 family)
# ==========================================================================
def mla_specs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    dh = cfg.resolved_head_dim          # nope dim per head (== value dim)
    dr = cfg.rope_head_dim
    r = cfg.kv_lora_rank
    return {
        "w_q_nope": ParamSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "w_q_rope": ParamSpec((d, h, dr), ("embed", "heads", "head_dim")),
        "w_dkv": ParamSpec((d, r), ("embed", "kv_lora")),
        "w_k_rope": ParamSpec((d, dr), ("embed", "head_dim")),
        "w_uk": ParamSpec((r, h, dh), ("kv_lora", "heads", "head_dim")),
        "w_uv": ParamSpec((r, h, dh), ("kv_lora", "heads", "head_dim")),
        "w_o": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
        "norm_kv": ParamSpec((r,), ("kv_lora",), init="ones"),
    }


def mla_latents(p: dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    """x (B,S,D) -> latent c_kv (B,S,r) [RMS-normed], k_rope (B,1,S,dr)."""
    from repro.models.layers import rms_norm

    dt = x.dtype
    c_kv = rms_norm(x @ p["w_dkv"].astype(dt), p["norm_kv"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,de->bse", x, p["w_k_rope"].astype(dt))[:, None]
    sin, cos = rotary_angles(positions, cfg.rope_head_dim, cfg.rope_theta)
    k_rope = apply_rotary(k_rope, sin[:, None], cos[:, None])
    return c_kv, k_rope


def mla_forward(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    impl: str,
    mode: str = "causal",
) -> jnp.ndarray:
    """Full-sequence MLA: materialize per-head K/V from the latent."""
    dt = x.dtype
    dh, dr = cfg.resolved_head_dim, cfg.rope_head_dim
    c_kv, k_rope = mla_latents(p, cfg, x, positions)

    q_nope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_nope"].astype(dt))
    q_rope = jnp.einsum("bsd,dhe->bhse", x, p["w_q_rope"].astype(dt))
    sin, cos = rotary_angles(positions, dr, cfg.rope_theta)
    q_rope = apply_rotary(q_rope, sin[:, None], cos[:, None])

    k_nope = jnp.einsum("bsr,rhe->bhse", c_kv, p["w_uk"].astype(dt))
    v = jnp.einsum("bsr,rhe->bhse", c_kv, p["w_uv"].astype(dt))

    h = cfg.num_heads
    k_rope_b = jnp.broadcast_to(k_rope, (*k_rope.shape[:1], h, *k_rope.shape[2:]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    # Match the standard MLA scale: 1/sqrt(dh + dr).
    scale = (dh + dr) ** -0.5
    if impl == "full":
        out = full_attention(q, k, v, causal=(mode == "causal"), scale=scale)
    elif impl == "chunked":
        out = chunked_attention(q, k, v, causal=(mode == "causal"),
                                scale=scale, unroll=cfg.unroll_scans)
    else:
        ss = ss_config_from(cfg, causal=(mode == "causal"))
        out = spectral_shift_attention(q, k, v, ss, scale=scale)
    return jnp.einsum("bhse,hed->bsd", out, p["w_o"].astype(dt))
