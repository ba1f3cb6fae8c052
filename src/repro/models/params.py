"""Parameter-spec infrastructure: one source of truth for shapes, logical
sharding axes, initialization, and abstract (dry-run) parameter trees.

A model declares a nested dict of ``ParamSpec``; from it we derive
 * ``init_params``      — real arrays (reduced configs, CPU smoke tests)
 * ``abstract_params``  — ShapeDtypeStructs (dry-run lowering, no allocation)
 * ``logical_axes``     — pytree of logical-axis tuples consumed by
                          ``repro.distributed.sharding`` to build PartitionSpecs.
"""
from __future__ import annotations

import hashlib
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


class ParamSpec(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]  # logical axis names, len == len(shape)
    init: str = "normal"             # normal | zeros | ones
    scale: Optional[float] = None    # stddev override (default: fan-in)
    dtype: Optional[Any] = None      # per-param dtype override


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _map_specs(fn, specs):
    return jax.tree.map(fn, specs, is_leaf=_is_spec)


def _fan_in_scale(spec: ParamSpec) -> float:
    if spec.scale is not None:
        return spec.scale
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    return fan_in**-0.5


def init_params(specs, key: jax.Array, dtype=jnp.float32):
    """Materialize real parameters. Each leaf gets an independent stream
    derived from its tree path, so adding parameters never reshuffles
    existing initializations."""
    paths_and_specs, treedef = jax.tree.flatten_with_path(
        specs, is_leaf=_is_spec
    )
    leaves = []
    for path, spec in paths_and_specs:
        pdt = spec.dtype or dtype
        if spec.init == "zeros":
            leaves.append(jnp.zeros(spec.shape, pdt))
        elif spec.init == "ones":
            leaves.append(jnp.ones(spec.shape, pdt))
        else:
            digest = hashlib.md5(jax.tree_util.keystr(path).encode()).digest()
            sub = jax.random.fold_in(key, int.from_bytes(digest[:4], "little"))
            # Drawn in the target dtype: a float32 draw of a large bf16
            # leaf would double its peak footprint. float32 streams are
            # unchanged by this.
            arr = jax.random.normal(sub, spec.shape, pdt)
            leaves.append((arr * _fan_in_scale(spec)).astype(pdt))
    return jax.tree.unflatten(treedef, leaves)


def abstract_params(specs, dtype=jnp.float32):
    """ShapeDtypeStruct tree — used by the dry-run; allocates nothing."""
    return _map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype), specs
    )


def logical_axes(specs):
    """Pytree of logical-axis tuples, aligned with the parameter tree."""
    return _map_specs(lambda s: s.axes, specs)


def stack_layer_specs(layer_specs, num_layers: int):
    """Prepend a scanned ``layers`` dimension to every spec in a layer tree."""
    return _map_specs(
        lambda s: ParamSpec(
            shape=(num_layers, *s.shape),
            axes=("layers", *s.axes),
            init=s.init,
            scale=s.scale,
            dtype=s.dtype,
        ),
        layer_specs,
    )


def count_params(specs) -> int:
    total = 0
    for spec in jax.tree.leaves(specs, is_leaf=_is_spec):
        n = 1
        for s in spec.shape:
            n *= s
        total += n
    return total
