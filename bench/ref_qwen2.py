"""Plain reference of a served Qwen2-style decoder with spectral-shift
attention, written from the published equations and nothing of the
program under test.

Model (Qwen2, arXiv:2407.10671): token embedding; per layer RMSNorm ->
grouped-query attention with QKV bias and rotary positions (rotate-half,
theta from the configuration) -> residual; RMSNorm -> SwiGLU MLP ->
residual; final RMSNorm; untied output head. Query head ``h`` reads
key/value head ``h // (heads / kv_heads)``.

Attention (Beyond Nystromformer, arXiv:2103.05638) in the two forms a
server runs:

* over a prompt of ``n > c`` tokens, bidirectional spectral shifting:
  landmarks are the means of ``c`` contiguous segments of ``ceil(n / c)``
  tokens, ``F = softmax(q K~^T s)``, ``A = softmax(Q~ K~^T s)``,
  ``B = softmax(Q~ K^T s)``, ``out = F U (B V) + delta V`` with
  ``Z = pinv(A)`` by six steps of the quartic Newton-Schulz iteration
  from ``A^T / (||A||_1 ||A||_inf)``, soft rank ``tr(A Z)``,
  ``delta = max(tr A - tr(A Z A), 0) / max(c - tr(A Z), 0.01)`` and
  ``U = Z (I - delta Z)``. A prompt of ``n <= c`` tokens attends exactly.
* for a generated token at position ``p``: landmarks are segment means
  over the serving horizon (segments of ``ceil(max_seq / c)`` positions),
  counting positions ``0..p`` only; landmarks of segments not yet reached
  are masked out of ``F`` and ``A`` (their block of ``A`` is the
  identity), ``B`` is the softmax over keys ``0..p``, and
  ``out = F U (B V) + delta v_p``.

Everything is computed in float32 at the highest matmul precision. The
``quant`` argument rounds the inputs of every matmul that the served
model runs in bfloat16 (projections, MLP, output head, attention scores
and values) to a lower precision first: the control of the comparison.
The c x c core stays in float32, as the served model states.

Runs one layer at a time (``lax.scan`` over the stacked layers), so only
one layer's weights are ever widened to float32.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
# Programs are compiled per padded length, in steps of this many tokens.
BUCKET = 2048


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    landmarks: int
    pinv_iters: int
    max_seq: int

    @classmethod
    def from_model(cls, model: dict, max_seq: int) -> "Dims":
        return cls(
            layers=model["num_layers"], d_model=model["d_model"],
            heads=model["num_heads"], kv_heads=model["num_kv_heads"],
            head_dim=model.get("head_dim") or
            model["d_model"] // model["num_heads"],
            d_ff=model["d_ff"], vocab=model["vocab_size"],
            rope_theta=model["rope_theta"],
            norm_eps=model.get("norm_eps", 1e-6),
            landmarks=model["num_landmarks"],
            pinv_iters=model.get("pinv_iters", 6), max_seq=max_seq,
        )


# --------------------------------------------------------------------------
# precision of matmul inputs
# --------------------------------------------------------------------------
def _round_fp8(x):
    x = x.astype(F32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / FP8_MAX)
    return (x / s).astype(FP8).astype(F32) * s


@jax.custom_vjp
def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax / 448); in a
    gradient the incoming cotangent is rounded the same way, with its own
    scale, as a float8 training step would."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None),
            lambda _, g: (_round_fp8(g),))


ROUNDING = {"f32": lambda x: x.astype(F32), "fp8": _fp8}


def _mm(spec, a, b, quant):
    r = ROUNDING[quant]
    return jnp.einsum(spec, r(a), r(b), precision=HI,
                      preferred_element_type=F32)


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------
def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rotary(x, pos, theta):
    """x (..., n, d) at integer positions pos (n,): rotate-half."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax(s, mask=None):
    s = s.astype(F32)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    if mask is not None:
        e = jnp.where(mask, e, 0.0)
    return e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)


def _tr(x):
    return jnp.einsum("...ii->...", x)


def ss_core(a, iters):
    """(U, delta) of the c x c core A (..., c, c)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=F32)
    n1 = jnp.max(jnp.sum(jnp.abs(a), -2), -1)[..., None, None]
    ninf = jnp.max(jnp.sum(jnp.abs(a), -1), -1)[..., None, None]
    z = jnp.swapaxes(a, -1, -2) / jnp.maximum(n1 * ninf, 1e-30)
    mm = functools.partial(jnp.matmul, precision=HI)
    for _ in range(iters):
        az = mm(a, z)
        z = 0.25 * mm(z, 13 * eye - mm(az, 15 * eye - mm(az, 7 * eye - az)))
    az = mm(a, z)
    tail = _tr(a) - _tr(mm(az, a))
    delta = jnp.maximum(tail, 0.0) / jnp.maximum(c - _tr(az), 1e-2)
    delta = delta[..., None, None]
    return mm(z, eye - delta * z), delta


def _kv_to_heads(x, heads):
    """(Hkv, ...) -> (H, ...): query head h reads kv head h // group."""
    return jnp.repeat(x, heads // x.shape[0], axis=0)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def prompt_attention(q, k, v, n_valid, dims: Dims, quant, short: bool):
    """Bidirectional attention over the first ``n_valid`` of a padded
    prompt (``n_valid`` may be traced). q, k, v (H, n_pad, d), k and v
    already per query head. Rows past ``n_valid`` are not meaningful.
    ``short``: the prompt has at most ``c`` tokens and attends exactly."""
    n_pad, c = q.shape[1], dims.landmarks
    scale = dims.head_dim ** -0.5
    pos = jnp.arange(n_pad)
    keys = (pos < n_valid)[None, None, :]
    if short:
        p = softmax(_mm("hqd,hkd->hqk", q, k, quant) * scale, keys)
        return _mm("hqk,hkd->hqd", p, v, quant)
    seg = -(-n_valid // c)
    onehot = ((pos[None, :] // seg == jnp.arange(c)[:, None])
              & (pos < n_valid)[None, :]).astype(F32)
    counts = jnp.clip(n_valid - jnp.arange(c) * seg, 1, seg).astype(F32)
    q_l = jnp.einsum("cn,hnd->hcd", onehot, q, precision=HI) / counts[:, None]
    k_l = jnp.einsum("cn,hnd->hcd", onehot, k, precision=HI) / counts[:, None]
    f = softmax(_mm("hqd,hcd->hqc", q, k_l, quant) * scale)
    a = softmax(jnp.einsum("hcd,hed->hce", q_l, k_l, precision=HI) * scale)
    b = softmax(_mm("hcd,hkd->hck", q_l, k, quant) * scale, keys)
    u, delta = ss_core(a, dims.pinv_iters)
    bv = _mm("hck,hkd->hcd", b, v, quant)
    ubv = jnp.matmul(u, bv, precision=HI)
    return _mm("hqc,hcd->hqd", f, ubv, quant) + delta * v


def token_attention(q, k_all, v_all, q_all, p, dims: Dims, quant):
    """Spectral shifting for the generated token at position ``p``.
    q (H, d); k_all, v_all, q_all (H, S, d) hold positions 0..p (and
    anything beyond, which is masked)."""
    c, s_len = dims.landmarks, k_all.shape[1]
    scale = dims.head_dim ** -0.5
    seg = -(-dims.max_seq // c)
    t = jnp.arange(s_len)
    seen = t <= p
    onehot = ((t[None, :] // seg == jnp.arange(c)[:, None])
              & seen[None, :]).astype(F32)
    counts = jnp.clip(p + 1 - jnp.arange(c) * seg, 0, seg).astype(F32)
    valid = counts > 0
    denom = jnp.maximum(counts, 1.0)[:, None]
    q_l = jnp.einsum("cs,hsd->hcd", onehot, q_all, precision=HI) / denom
    k_l = jnp.einsum("cs,hsd->hcd", onehot, k_all, precision=HI) / denom
    f = softmax(_mm("hd,hcd->hc", q, k_l, quant) * scale, valid[None, :])
    a_mask = valid[:, None] & valid[None, :]
    a = softmax(jnp.einsum("hcd,hed->hce", q_l, k_l, precision=HI) * scale,
                a_mask[None])
    a = jnp.where(a_mask[None], a, jnp.eye(c, dtype=F32))
    b = softmax(_mm("hcd,hsd->hcs", q_l, k_all, quant) * scale,
                seen[None, None, :])
    u, delta = ss_core(a, dims.pinv_iters)
    bv = _mm("hcs,hsd->hcd", b, v_all, quant)
    ubv = jnp.matmul(u, bv, precision=HI)
    v_p = jax.lax.dynamic_index_in_dim(v_all, p, axis=1, keepdims=False)
    return _mm("hc,hcd->hd", f, ubv, quant) + delta[..., 0] * v_p


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------
def _qkv(lp, h, pos, dims: Dims, quant):
    """h (n, D) normed -> q (H, n, d), k/v (Hkv, n, d)."""
    a = lp["attn"]
    q = _mm("nd,dhe->hne", h, a["w_q"], quant) + a["b_q"].astype(F32)[:, None]
    k = _mm("nd,dhe->hne", h, a["w_k"], quant) + a["b_k"].astype(F32)[:, None]
    v = _mm("nd,dhe->hne", h, a["w_v"], quant) + a["b_v"].astype(F32)[:, None]
    return (rotary(q, pos, dims.rope_theta), rotary(k, pos, dims.rope_theta),
            v)


def _mlp(lp, x, dims: Dims, quant):
    """Residual SwiGLU block, over blocks of ``BUCKET`` rows at a time when
    the prompt is longer (rows are independent)."""
    m = lp["mlp"]

    def rows(x):
        h = rms_norm(x, lp["norm_mlp"], dims.norm_eps)
        g = _mm("nd,df->nf", h, m["w_gate"], quant)
        up = _mm("nd,df->nf", h, m["w_up"], quant)
        return x + _mm("nf,fd->nd", jax.nn.silu(g) * up, m["w_down"], quant)

    n = x.shape[0]
    if n <= BUCKET:
        return rows(x)
    out = jax.lax.map(rows, x.reshape(n // BUCKET, BUCKET, x.shape[1]))
    return out.reshape(x.shape)


def _head(params, x, dims: Dims, quant):
    h = rms_norm(x, params["final_norm"], dims.norm_eps)
    return _mm("nd,dv->nv", h, params["lm_head"], quant)[:, : dims.vocab]


def _embed(params, tokens, quant):
    return ROUNDING[quant](jnp.take(params["embed"], tokens, axis=0))


@functools.partial(jax.jit, static_argnames=("dims", "quant", "short"))
def prefill(params, tokens, n_valid, dims: Dims, quant="f32", short=False):
    """The first ``n_valid`` of the padded prompt ``tokens`` (n_pad,).
    Returns the logits at position ``n_valid - 1`` (V,) and per-layer q, k,
    v (L, H|Hkv, n_pad, d)."""
    pos = jnp.arange(tokens.shape[0])

    def layer(x, lp):
        h = rms_norm(x, lp["norm_attn"], dims.norm_eps)
        q, k, v = _qkv(lp, h, pos, dims, quant)
        kh, vh = _kv_to_heads(k, dims.heads), _kv_to_heads(v, dims.heads)
        o = prompt_attention(q, kh, vh, n_valid, dims, quant, short)
        x = x + _mm("hne,hed->nd", o, lp["attn"]["w_o"], quant)
        return _mlp(lp, x, dims, quant), (q, k, v)

    x, (qs, ks, vs) = jax.lax.scan(layer, _embed(params, tokens, quant),
                                   params["layers"])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
    return _head(params, last, dims, quant)[0], qs, ks, vs


@functools.partial(jax.jit, static_argnames=("dims", "quant"),
                   donate_argnames=("qs", "ks", "vs"))
def step(params, qs, ks, vs, token, p, dims: Dims, quant="f32"):
    """Feed ``token`` at position ``p``; qs (L, H, S, d), ks/vs
    (L, Hkv, S, d) hold positions < p. Returns the next-token logits and
    the states with position p written."""
    pos = jnp.reshape(p, (1,))

    def layer(x, xs):
        lp, q_all, k_all, v_all = xs
        h = rms_norm(x, lp["norm_attn"], dims.norm_eps)
        q, k, v = _qkv(lp, h, pos, dims, quant)
        q_all = jax.lax.dynamic_update_slice_in_dim(q_all, q, p, axis=1)
        k_all = jax.lax.dynamic_update_slice_in_dim(k_all, k, p, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(v_all, v, p, axis=1)
        o = token_attention(
            q[:, 0], _kv_to_heads(k_all, dims.heads),
            _kv_to_heads(v_all, dims.heads), q_all, p, dims, quant,
        )
        x = x + _mm("he,hed->d", o, lp["attn"]["w_o"], quant)[None]
        return _mlp(lp, x, dims, quant), (q_all, k_all, v_all)

    x, (qs, ks, vs) = jax.lax.scan(
        layer, _embed(params, jnp.reshape(token, (1,)), quant),
        (params["layers"], qs, ks, vs),
    )
    return _head(params, x, dims, quant)[0], qs, ks, vs


def _bucket(n: int) -> int:
    return -(-n // BUCKET) * BUCKET


def score(params, prompt, served, dims: Dims, quant="f32"):
    """Teacher-force ``prompt`` then ``served`` (the tokens the server
    produced). Returns the logits that precede each served token, as a
    (len(served), V) host array: row j is what the model predicts after
    prompt + served[:j]."""
    n, m = len(prompt), len(served)
    s_len = _bucket(n + m)
    tokens = np.zeros(s_len, np.int32)
    tokens[:n] = prompt
    short = n <= dims.landmarks
    n_pad = dims.landmarks if short else _bucket(n)
    logits, qs, ks, vs = prefill(params, jnp.asarray(tokens[:n_pad]),
                                 jnp.int32(n), dims, quant, short)
    rows = [np.asarray(logits)]
    if m > 1:
        pad = [(0, 0), (0, 0), (0, s_len - qs.shape[2]), (0, 0)]
        qs, ks, vs = (jnp.pad(x, pad) for x in (qs, ks, vs))
        for j in range(m - 1):
            logits, qs, ks, vs = step(params, qs, ks, vs, jnp.int32(served[j]),
                                      jnp.int32(n + j), dims, quant)
            rows.append(np.asarray(logits))
    return np.stack(rows)


def served_gap(ref_logits, served) -> np.ndarray:
    """Per token: how far the served token's reference logit lies below
    the reference's best (0 where the served token is the best)."""
    ref = np.asarray(ref_logits, np.float64)
    idx = np.asarray(served)[:, None]
    return ref.max(-1) - np.take_along_axis(ref, idx, -1)[:, 0]
