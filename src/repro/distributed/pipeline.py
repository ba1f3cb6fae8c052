"""GPipe-style pipeline parallelism on a mesh axis, via shard_map + ppermute.

At 1000+-node scale the cross-pod ICI/DCN links are the scarce resource;
mapping pipeline stages onto the ``pod`` axis replaces the per-step gradient
all-reduce over the slow links with point-to-point activation transfers
(microbatch ping-pong), which is the standard multi-pod recipe. The schedule
here is the classic GPipe fill-drain expressed as a ``lax.scan`` over
``num_micro + num_stages - 1`` ticks:

    tick t, stage s computes microbatch (t - s); activations rotate to the
    next stage with one ``ppermute`` per tick.

Weights are stacked per-stage on the leading axis and sharded over the pipe
axis, so each device only holds (and only runs) its own stage's layers —
inside ``shard_map`` the stage picks its slice implicitly.

This module is mesh-shape agnostic: tests run it on a (4,)-device "pipe"
mesh (forced host devices); the production launcher maps it onto ``pod``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stages(layer_params_list: list, num_stages: int):
    """[L layer pytrees] -> pytree with leading (num_stages, L/num_stages)."""
    L = len(layer_params_list)
    if L % num_stages:
        raise ValueError(f"{L} layers not divisible into {num_stages} stages")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params_list)
    return jax.tree.map(
        lambda x: x.reshape(num_stages, L // num_stages, *x.shape[1:]), stacked
    )


def make_pipeline_forward(
    layer_fn: Callable,
    mesh: Mesh,
    axis: str = "pipe",
):
    """Build ``f(stage_params, microbatches) -> outputs``.

    ``layer_fn(layer_params, x) -> x`` is one layer; each stage scans it over
    its local layer stack. ``stage_params`` leaves are (S, L/S, ...), sharded
    over ``axis``; ``microbatches`` is (M, mb, ...) replicated. Output is
    (M, mb, ...) replicated (psum-broadcast from the last stage).
    """
    num_stages = mesh.shape[axis]

    def stage_fn(local_layers, x):
        def body(y, lp):
            return layer_fn(lp, y), None

        y, _ = jax.lax.scan(body, x, local_layers)
        return y

    def shard_body(stage_params, microbatches):
        # Inside shard_map: stage_params leaves are (1, L/S, ...) — this
        # stage's slice; microbatches (M, mb, ...) full (replicated).
        local_layers = jax.tree.map(lambda p: p[0], stage_params)
        s = jax.lax.axis_index(axis)
        num_micro = microbatches.shape[0]
        ticks = num_micro + num_stages - 1
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        zero = jnp.zeros_like(microbatches[0])

        def tick(carry, t):
            buf = carry  # activation handed to this stage this tick
            mb_idx = jnp.clip(t, 0, num_micro - 1)
            x_in = jnp.where(
                s == 0,
                jax.lax.dynamic_index_in_dim(
                    microbatches, mb_idx, 0, keepdims=False
                ),
                buf,
            )
            y = stage_fn(local_layers, x_in)
            nxt = jax.lax.ppermute(y, axis, perm)
            return nxt, y

        _, ys = jax.lax.scan(tick, zero, jnp.arange(ticks))
        # Last stage's outputs at ticks [S-1, S-1+M) are microbatches [0, M).
        outs = jax.lax.dynamic_slice_in_dim(ys, num_stages - 1, num_micro, 0)
        # Broadcast the last stage's result to every stage (cheap at test
        # scale; production computes the loss on the last stage instead).
        outs = jnp.where(s == num_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    def pipeline_forward(stage_params, microbatches):
        in_specs = (
            jax.tree.map(lambda _: P(axis), stage_params),
            P(),
        )
        fn = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P(),
            check_vma=False,
        )
        return fn(stage_params, microbatches)

    return pipeline_forward


def reference_forward(layer_fn: Callable, layer_params_list: list, x: jnp.ndarray):
    """Sequential oracle for the pipeline: run all layers on the full batch."""
    for lp in layer_params_list:
        x = layer_fn(lp, x)
    return x
