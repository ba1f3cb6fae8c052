"""Plain reference of one training job's first steps: a decoder LM with
segment-causal spectral-shift attention, next-token cross entropy and
AdamW, written from the published equations and nothing of the program.

Model: token embedding; per layer RMSNorm -> multi-head attention with
rotary positions -> residual; RMSNorm -> MLP -> residual; final RMSNorm;
output head over every row of the (padded) vocabulary. The MLP is SwiGLU
(``act: swiglu``) or, with ``act: gelu``, BERT's two matrices with biases
and the tanh form of GELU (Hendrycks and Gimpel, arXiv:1606.08415).

Attention (Beyond Nystromformer, arXiv:2103.05638, with segment-causal
masks): ``n`` tokens in ``c`` segments of ``ceil(n / c)``; landmarks are
the segment means; query ``t`` sees landmarks of segments up to its own,
landmark ``r`` sees landmarks ``e <= r`` in ``A`` and keys of segments up
to ``r`` in ``B``; ``U = Z (I - delta Z)`` from the six-step pinv of the
masked ``A`` (``ref_qwen2.ss_core``), kept lower-triangular;
``out = F U (B V) + delta V``.

Loss: mean over every position whose next token is not 0 (padding) of
``logsumexp(logits) - logit[next token]``. Optimizer: AdamW with global
norm clipping, bias correction, decoupled weight decay on every leaf, and
a linear-warmup cosine learning rate (``TrainConfig``'s fields).

The gradient is taken one row of the batch at a time and summed, so the
float32 logits of one row are the largest array it holds.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ref_qwen2 import F32, HI, ROUNDING, _mm, rms_norm, rotary, softmax, ss_core


def causal_attention(q, k, v, c, iters, quant):
    """q, k, v (H, n, d) -> (H, n, d), segment-causal spectral shifting."""
    h, n, d = q.shape
    scale = d ** -0.5
    if n <= c:
        mask = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
        p = softmax(_mm("hqd,hkd->hqk", q, k, quant) * scale, mask[None])
        return _mm("hqk,hkd->hqd", p, v, quant)
    seg = -(-n // c)
    pos = jnp.arange(n)
    r = jnp.arange(c)
    onehot = (pos[None, :] // seg == r[:, None]).astype(F32)
    counts = jnp.clip(n - r * seg, 1, seg).astype(F32)
    q_l = jnp.einsum("cn,hnd->hcd", onehot, q, precision=HI) / counts[:, None]
    k_l = jnp.einsum("cn,hnd->hcd", onehot, k, precision=HI) / counts[:, None]
    f_mask = r[None, :] <= (pos // seg)[:, None]
    a_mask = r[:, None] >= r[None, :]
    b_mask = pos[None, :] < (r[:, None] + 1) * seg
    f = softmax(_mm("hqd,hcd->hqc", q, k_l, quant) * scale, f_mask[None])
    a = softmax(jnp.einsum("hcd,hed->hce", q_l, k_l, precision=HI) * scale,
                a_mask[None])
    b = softmax(_mm("hcd,hkd->hck", q_l, k, quant) * scale, b_mask[None])
    u, delta = ss_core(a, iters)
    u = jnp.where(a_mask[None], u, 0.0)
    ubv = jnp.matmul(u, _mm("hck,hkd->hcd", b, v, quant), precision=HI)
    return _mm("hqc,hcd->hqd", f, ubv, quant) + delta * v


def gelu(x):
    """GELU, tanh form, as BERT computes it."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def mlp(y, mp, act: str, quant):
    if act == "gelu":
        z = gelu(_mm("nd,df->nf", y, mp["w_up"], quant) + mp["b_up"])
        return _mm("nf,fd->nd", z, mp["w_down"], quant) + mp["b_down"]
    z = jax.nn.silu(_mm("nd,df->nf", y, mp["w_gate"], quant)) * \
        _mm("nd,df->nf", y, mp["w_up"], quant)
    return _mm("nf,fd->nd", z, mp["w_down"], quant)


def row_loss_sum(params, tokens, m: dict, quant):
    """Summed next-token cross entropy of one row and its count."""
    n = tokens.shape[0]
    h = m["num_heads"]
    d = m.get("head_dim") or m["d_model"] // h
    pos = jnp.arange(n)
    eps = m.get("norm_eps", 1e-6)

    @jax.checkpoint
    def layer(x, lp):
        a = lp["attn"]
        y = rms_norm(x, lp["norm_attn"], eps)
        q = rotary(_mm("nd,dhe->hne", y, a["w_q"], quant), pos, m["rope_theta"])
        k = rotary(_mm("nd,dhe->hne", y, a["w_k"], quant), pos, m["rope_theta"])
        v = _mm("nd,dhe->hne", y, a["w_v"], quant)
        g = h // k.shape[0]
        k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
        o = causal_attention(q, k, v, m["num_landmarks"],
                             m.get("pinv_iters", 6), quant)
        x = x + _mm("hne,hed->nd", o, a["w_o"], quant)
        y = rms_norm(x, lp["norm_mlp"], eps)
        return x + mlp(y, lp["mlp"], m.get("act", "swiglu"), quant), None

    x = ROUNDING[quant](jnp.take(params["embed"], tokens, axis=0))
    x, _ = jax.lax.scan(layer, x, params["layers"])
    logits = _mm("nd,dv->nv", rms_norm(x, params["final_norm"], eps),
                 params["lm_head"], quant)
    lg, tgt = logits[:-1], tokens[1:]
    ce = jax.scipy.special.logsumexp(lg, -1) - \
        jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0]
    mask = (tgt != 0).astype(F32)
    return jnp.sum(ce * mask), jnp.sum(mask)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def row_grad(params, tokens, m, quant):
    """(loss sum, count, gradient of the loss sum) of one row."""
    (s, cnt), g = jax.value_and_grad(
        lambda p: row_loss_sum(p, tokens, dict(m), quant), has_aux=True,
    )(params)
    return s, cnt, g


def loss_and_grad(params, batch: np.ndarray, m: dict, quant="f32"):
    """Mean next-token loss of the batch and its gradient, a row at a
    time."""
    key = tuple(sorted(m.items()))
    total, count, grad = 0.0, 0.0, None
    for row in np.asarray(batch):
        s, cnt, g = row_grad(params, jnp.asarray(row), key, quant)
        total += float(s)
        count += float(cnt)
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    count = max(count, 1.0)
    return total / count, jax.tree.map(lambda x: x / count, grad)


def learning_rate(step: int, t: dict) -> float:
    """Linear warmup to the peak, then cosine decay to a tenth of it."""
    peak, warm, total = t["learning_rate"], t["warmup_steps"], t["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw(params, grad, state, step: int, t: dict):
    """One AdamW update (``step`` counts from 1). Returns the new params,
    the new (m, v) and the clipped gradient the moments took in."""
    leaves = jax.tree.leaves(grad)
    norm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in leaves)))
    clip = min(1.0, t["grad_clip"] / max(norm, 1e-9))
    b1, b2 = t["beta1"], t["beta2"]
    lr = learning_rate(step, t)
    g = jax.tree.map(lambda x: x * clip, grad)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, state[0], g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, state[1], g)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    new = jax.tree.map(
        lambda p, a, s: p - lr * ((a / bc1) / (jnp.sqrt(s / bc2) + 1e-8)
                                  + t["weight_decay"] * p),
        params, m, v)
    return new, (m, v), g


def first_steps(params, batches, m: dict, t: dict, quant="f32"):
    """The first ``len(batches)`` steps from ``params`` (float32). Returns
    the losses, the clipped first gradient, and the params after them."""
    params = jax.tree.map(lambda x: jnp.asarray(x, F32), params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (zeros, zeros)
    losses, first = [], None
    for i, batch in enumerate(batches):
        loss, grad = loss_and_grad(params, batch, m, quant)
        params, state, clipped = adamw(params, grad, state, i + 1, t)
        losses.append(loss)
        if first is None:
            first = clipped
    return losses, first, params
