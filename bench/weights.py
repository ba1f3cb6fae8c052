"""Seeded weights for a served model, made on the device in one jitted call.

The benchmark owns the weight distribution; the program only supplies the
tree of parameter shapes it expects. Every matrix is drawn as in GPT-2
(Radford et al., 2019): normal with standard deviation 0.02, and the two
projections that write into the residual stream (``w_o``, ``w_down``)
scaled by ``1 / sqrt(2 * layers)``. Norm gains are ones. Random weights
drawn with the program's own fan-in rule (``1 / sqrt(shape[-2])``, which
for a ``(d_model, heads, head_dim)`` projection is ``1 / sqrt(heads)``)
give attention scores so sharp that sixteen layers amplify rounding into
logits that share nothing across routes; this distribution keeps the
random model well conditioned, so a comparison with a reference means
something. Speed does not depend on the values.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

STD = 0.02
RESIDUAL_OUT = ("w_o", "w_down")
ONES = ("norm_attn", "norm_mlp", "final_norm")


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def leaf_std(name: str, num_layers: int) -> float:
    """Standard deviation of a drawn leaf, by its name in the tree."""
    if name in RESIDUAL_OUT:
        return STD / math.sqrt(2 * num_layers)
    return STD


def make_weights(shapes, seed: int, num_layers: int, dtype=jnp.bfloat16):
    """``shapes``: a tree of ``jax.ShapeDtypeStruct`` (or anything with
    ``.shape``). Returns the tree of ``dtype`` arrays drawn from ``seed``.
    Each leaf has its own stream, keyed by its path, so the tree's order
    does not matter."""
    paths, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: hasattr(x, "shape")
    )
    plan = []
    for path, leaf in paths:
        name = _leaf_name(path)
        key_id = zlib.crc32(jax.tree_util.keystr(path).encode())
        plan.append((tuple(leaf.shape), name, key_id))

    def draw(key):
        out = []
        for shape, name, key_id in plan:
            if name in ONES:
                out.append(jnp.ones(shape, dtype))
                continue
            sub = jax.random.fold_in(key, key_id)
            std = leaf_std(name, num_layers)
            out.append(jax.random.normal(sub, shape, dtype) * std)
        return out

    leaves = jax.jit(draw)(seed_key(seed))
    return jax.tree.unflatten(treedef, leaves)


def seed_key(seed: int):
    """A JAX key for any non-negative seed, including ones past 32 bits."""
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)
