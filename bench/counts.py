"""Work the algorithm requires, from shapes alone.

Each function counts the floating-point operations (a multiply-add is two)
and the bytes that a call must move at least, whatever implements it: a
kernel that reads a tensor twice, pads, or broadcasts key/value heads
moves more than this and shows a lower share of its roofline. Nothing here
reads the program's compiled cost.

Shapes: ``h`` query heads, ``hkv`` key/value heads, ``d`` head width,
``c`` landmarks, ``n`` keys (or prompt tokens), ``b`` bytes per element of
the stored keys and values.
"""
from __future__ import annotations


def core_flops(c: int, iters: int) -> float:
    """The c x c spectral-shift core of one head: ``iters`` quartic
    Newton-Schulz steps (four c x c products each), the soft rank and
    shift (two), and U (one)."""
    return 2.0 * c ** 3 * (4 * iters + 3)


# -- kernels ----------------------------------------------------------------
def landmark_summary(n, h, hkv, d, c, b=2):
    """B-side of spectral shifting for ``h`` heads over ``n`` keys:
    scores of ``c`` landmark queries against every key, and the
    softmax-weighted sum of the values. Reads each key and value once."""
    flops = 4.0 * h * c * n * d
    bytes_ = 2.0 * hkv * n * d * b + 2.0 * h * c * d * 4
    return flops, bytes_


def query_side(n, h, d, c, b=2):
    """F-side for ``n`` queries of ``h`` heads: scores against ``c``
    landmark keys and the product with the c x d summary. Reads the
    queries and writes the outputs once."""
    flops = 4.0 * h * n * c * d
    bytes_ = 2.0 * h * n * d * b + 2.0 * h * c * d * 4
    return flops, bytes_


def paged_row_stats(pos, h, hkv, d, b=2):
    """One decoding lane at position ``pos``, one layer: the active
    landmark row of each head against keys ``0..pos-1`` of the paged
    cache (scores and weighted values). Reads those keys and values
    once."""
    flops = 4.0 * h * pos * d
    bytes_ = 2.0 * hkv * pos * d * b
    return flops, bytes_


def landmark_summary_bwd(n, h, hkv, d, c, b=2):
    """Gradient of ``landmark_summary`` with respect to the landmark
    queries, keys and values: twice the forward products."""
    f, by = landmark_summary(n, h, hkv, d, c, b)
    return 2.0 * f, 2.0 * by


def query_side_bwd(n, h, d, c, b=2):
    """Gradient of ``query_side`` with respect to the queries, landmark
    keys and summary: twice the forward products, reading the output
    gradient as well."""
    f, by = query_side(n, h, d, c, b)
    return 2.0 * f, by + h * n * d * b


# -- models -----------------------------------------------------------------
def layer_matmul_params(m: dict) -> int:
    """Weights multiplied per token in one decoder layer (SwiGLU MLP,
    grouped-query attention)."""
    d, hd = m["d_model"], head_dim(m)
    h, hkv, ff = m["num_heads"], m["num_kv_heads"], m["d_ff"]
    mlp = (3 if m.get("act", "swiglu") == "swiglu" else 2) * d * ff
    return d * h * hd * 2 + d * hkv * hd * 2 + mlp


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def decode_token_flops(m: dict, pos: int, max_seq: int) -> float:
    """One generated token at position ``pos``: every layer's projections
    and MLP, the output head, and spectral-shift decode attention (the
    active landmark row over ``pos + 1`` keys, F, A, the core, U BV)."""
    h, d, c = m["num_heads"], head_dim(m), m["num_landmarks"]
    attn = h * (4.0 * (pos + 1) * d + 4.0 * c * d + 4.0 * c * c * d
                + core_flops(c, m.get("pinv_iters", 6)))
    layer = 2.0 * layer_matmul_params(m) + attn
    return m["num_layers"] * layer + 2.0 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: dict, n: int) -> float:
    """A whole prompt of ``n`` tokens: projections and MLP for every
    token, bidirectional spectral-shift attention over the prompt (plus
    the decode state's second landmark summary over it), and the output
    head for the last position only, which is all a server needs."""
    h, d, c = m["num_heads"], head_dim(m), m["num_landmarks"]
    attn = h * (2 * 4.0 * c * n * d + 4.0 * n * c * d + 4.0 * c * c * d
                + core_flops(c, m.get("pinv_iters", 6)))
    layer = 2.0 * layer_matmul_params(m) * n + attn
    return m["num_layers"] * layer + 2.0 * m["d_model"] * m["vocab_size"]


def train_token_flops(m: dict, seq: int) -> float:
    """Forward and backward per token of a training step (three times the
    forward; recomputation not counted): projections, MLP, output head
    over every token, and bidirectional spectral-shift attention."""
    h, d, c = m["num_heads"], head_dim(m), m["num_landmarks"]
    attn_per_token = h * (8.0 * c * d + 4.0 * c * d)
    core = h * (4.0 * c * c * d + core_flops(c, m.get("pinv_iters", 6))) / seq
    fwd = (m["num_layers"] * (2.0 * layer_matmul_params(m) + attn_per_token
                              + core)
           + 2.0 * m["d_model"] * m["vocab_size"])
    return 3.0 * fwd
