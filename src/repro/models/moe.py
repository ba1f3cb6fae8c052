"""Mixture-of-Experts FFN (DeepSeek-V2 / Kimi-K2 style: shared + routed,
top-k, capacity-bounded token dropping).

Dispatch uses scatter/gather (k scatters of the token block) rather than the
GShard (G,S,E,C) one-hot einsum — the einsum form costs T*E*C*D MACs (an
~80x FLOP overhead at our configs) while scatter is O(T*k*D) data movement.
Under pjit, tokens are batch-sharded ("data") and expert weights are
expert-sharded ("data") + ff-sharded ("model"), so the buf einsum reshard is
the classic EP all-to-all, inserted by GSPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import mlp_forward, mlp_specs
from repro.models.params import ParamSpec


def moe_specs(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    specs = {
        "router": ParamSpec((d, e), ("embed", None), scale=d**-0.5),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed_unsharded", "moe_ff")),
        "w_up": ParamSpec((e, d, f), ("experts", "embed_unsharded", "moe_ff")),
        "w_down": ParamSpec((e, f, d), ("experts", "moe_ff", "embed_unsharded")),
    }
    if cfg.num_shared_experts:
        specs["shared"] = mlp_specs(d, cfg.moe_d_ff * cfg.num_shared_experts, "swiglu")
    return specs


def capacity(cfg: ModelConfig, seq_len: int) -> int:
    c = int(seq_len * cfg.top_k * cfg.capacity_factor / cfg.num_experts) + 1
    return max(cfg.top_k, min(c, seq_len))


def moe_forward(
    p: dict, cfg: ModelConfig, x: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,D) -> (out (B,S,D), aux load-balance loss (scalar))."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = capacity(cfg, s)
    dt = x.dtype

    gates = jax.nn.softmax(
        (x @ p["router"].astype(dt)).astype(jnp.float32), axis=-1
    )  # (B,S,E)
    top_w, top_i = jax.lax.top_k(gates, k)  # (B,S,k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch-style): E * <f_e, p_e>.
    me = jnp.mean(gates, axis=(0, 1))  # (E,)
    one_hot_all = jax.nn.one_hot(top_i, e, dtype=jnp.float32)  # (B,S,k,E)
    fe = jnp.mean(one_hot_all.sum(2), axis=(0, 1)) / k
    aux = e * jnp.sum(fe * me)

    # Slot assignment: position of each (token, choice) within its expert,
    # in token order, capacity-bounded.
    choice_hot = one_hot_all.reshape(b, s * k, e).astype(jnp.int32)
    pos = jnp.cumsum(choice_hot, axis=1) - 1  # (B,S*k,E)
    slot = jnp.sum(pos * choice_hot, axis=-1).reshape(b, s, k)  # (B,S,k)
    keep = (slot < cap).astype(dt)
    slot = jnp.clip(slot, 0, cap - 1)

    # Dispatch: k scatter-adds of the token block into (B,E,cap,D).
    buf = jnp.zeros((b, e, cap, d), dt)
    b_idx = jnp.arange(b)[:, None]
    for j in range(k):
        buf = buf.at[b_idx, top_i[..., j], slot[..., j]].add(
            x * keep[..., j : j + 1], mode="drop"
        )

    # Expert FFN (SwiGLU), batched over (B, E): the (B<->E) reshard here is
    # the EP all-to-all under pjit.
    h = jax.nn.silu(
        jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dt))
    ) * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dt))
    buf_out = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(dt))

    # Combine: gather each choice's slot back and mix with gate weights.
    out = jnp.zeros_like(x)
    for j in range(k):
        gathered = buf_out[b_idx, top_i[..., j], slot[..., j]]  # (B,S,D)
        out = out + gathered * (top_w[..., j, None].astype(dt) * keep[..., j : j + 1])

    if cfg.num_shared_experts:
        out = out + mlp_forward(p["shared"], x, "swiglu")
    return out, aux.astype(jnp.float32)


# ==========================================================================
# Explicit expert-parallel MoE (shard_map all-to-all dispatch)
# ==========================================================================
def moe_forward_ep(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Expert-parallel MoE: tokens move to experts via all-to-all.

    Under pure GSPMD sharding the capacity-buffer scatter makes the
    partitioner combine FULL-BATCH buffer contributions with per-scatter
    all-reduces — measured 15.1 GB x 8 scatters x layer on kimi-k2
    (EXPERIMENTS.md §Perf cell B). This implementation makes the intended
    communication pattern explicit with shard_map:

      * experts are sharded over the ``data`` axis (E_loc per shard) and
        replicated over ``model``/``pod``;
      * each shard packs its tokens into per-destination capacity buckets
        and exchanges them with ONE all-to-all over ``data`` — the payload
        is split over ``model`` first, so each model shard moves and
        computes 1/TP of the capacity slots (token-sliced expert FFN: the
        small d_ff stays unsharded, no per-layer TP psum on the buffer);
      * expert outputs return by the inverse all-to-all and a single cheap
        (B_loc, S, D) psum over ``model`` rebuilds the combined output.

    Per-layer traffic per device ~ 2 x (T·k·D / E-shards / TP) a2a
    + one (B_loc,S,D) psum, vs ~8 full-buffer all-reduces under GSPMD.
    Falls back to ``moe_forward`` outside a mesh context (CPU tests).
    """
    from repro.distributed.sharding import _mesh, spec_for

    from jax.sharding import PartitionSpec as P

    mesh = _mesh()
    if mesh is None or "data" not in mesh.axis_names:
        return moe_forward(p, cfg, x)
    # Experts shard over every non-TP mesh axis ("pod" included on the
    # multi-pod mesh — leaving them data-only replicates 1T of expert
    # weights + moments across pods, §Perf cell B it4).
    ep_axes = tuple(a for a in mesh.axis_names if a != "model")
    dp = 1
    for a in ep_axes:
        dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    e, k = cfg.num_experts, cfg.top_k
    if e % dp:
        return moe_forward(p, cfg, x)  # experts must tile the EP axes
    e_loc = e // dp

    # Specs: batch over (pod,data); experts over the same axes; everything
    # else rides along replicated (model splits happen inside, by slicing).
    x_spec = spec_for(("batch", "seq", None))
    w_spec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, None)
    shared_spec = jax.tree.map(lambda _: P(), p.get("shared", {}))

    def inner(x_loc, router, w_gate, w_up, w_down, shared):
        b_loc, s, d = x_loc.shape
        t = b_loc * s
        dt = x_loc.dtype
        xt = x_loc.reshape(t, d)
        midx = jax.lax.axis_index("model") if tp > 1 else 0

        gates = jax.nn.softmax(
            (xt @ router.astype(dt)).astype(jnp.float32), axis=-1
        )  # (T, E)
        top_w, top_i = jax.lax.top_k(gates, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        # Load-balance aux (global means via psum over the token axes).
        me = jnp.mean(gates, axis=0)
        fe = jnp.mean(
            jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(1), axis=0
        ) / k
        tok_axes = tuple(a for a in mesh.axis_names if a != "model")
        me = jax.lax.pmean(me, tok_axes)
        fe = jax.lax.pmean(fe, tok_axes)
        aux = e * jnp.sum(fe * me)

        # Capacity per (source shard, expert), padded to a multiple of TP so
        # the slot dimension splits evenly over the model axis.
        cap = int(t * k * cfg.capacity_factor / e) + 1
        cap = max(cap, k)
        cap = -(-cap // tp) * tp

        # Slot of each (token, choice) within its expert bucket. Choice-major
        # cumsum (k separate (T, E) passes) keeps tensors at (T, E) instead
        # of (T*k, E) and lets dispatch scatter straight from xt — the
        # (T*k, D) fp32 payload materialization was the dominant memory term
        # of the first EP cut (15 GB/layer on kimi, §Perf cell B it2).
        base = jnp.zeros((e,), jnp.int32)
        slots, keeps = [], []
        for j in range(k):
            oh = jax.nn.one_hot(top_i[:, j], e, dtype=jnp.int32)  # (T, E)
            pos = jnp.cumsum(oh, axis=0) - 1 + base[None, :]
            slots.append(jnp.sum(pos * oh, axis=-1))              # (T,)
            base = base + oh.sum(axis=0)
            keeps.append(slots[-1] < cap)

        # Pack tokens into (dp, E_loc, cap//tp, D) send buckets, model-sliced
        # on the cap axis: this shard only fills/sends its cap/TP band.
        # Dispatch payload moves in the compute dtype (bf16 on TPU).
        send = jnp.zeros((dp, e_loc, cap // tp, d), dt)
        dest_l, ein_l, slotb_l, use_l = [], [], [], []
        for j in range(k):
            ej = top_i[:, j]
            slot = jnp.clip(slots[j], 0, cap - 1)
            band = (slot // (cap // tp)) == midx if tp > 1 else \
                jnp.ones_like(keeps[j])
            use = keeps[j] & band
            dest_l.append(ej // e_loc)
            ein_l.append(ej % e_loc)
            slotb_l.append(slot % (cap // tp))
            use_l.append(use)
            send = send.at[dest_l[j], ein_l[j], slotb_l[j]].add(
                xt * use[:, None].astype(dt), mode="drop"
            )

        # Exchange over data: dim 0 (destination) splits, received buffers
        # stack along a new source dim -> (dp, e_loc, cap//tp, d) where dim 0
        # now indexes the SOURCE shard.
        recv = jax.lax.all_to_all(
            send, ep_axes, split_axis=0, concat_axis=0, tiled=True
        ) if dp > 1 else send

        # Local expert FFN on (e_loc, dp * cap//tp, d), full d_ff (no TP).
        buf = recv.transpose(1, 0, 2, 3).reshape(e_loc, dp * (cap // tp), d)
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt))
        ) * jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
        out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))

        # Inverse exchange: back to (dp, e_loc, cap//tp, d) by source shard.
        out = out.reshape(e_loc, dp, cap // tp, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(
            out, ep_axes, split_axis=0, concat_axis=0, tiled=True
        ) if dp > 1 else out
        # back[dest, e_in, slot_b] is this shard's token results.

        # Combine the k choices (masked to this model shard's band), then
        # psum over model to merge the TP-sliced bands. Per-choice gathers
        # keep the working set at (T, D).
        y = jnp.zeros((t, d), jnp.float32)
        for j in range(k):
            gathered = back[dest_l[j], ein_l[j], slotb_l[j]]  # (T, D)
            wj = top_w[:, j] * use_l[j].astype(jnp.float32)
            y = y + gathered.astype(jnp.float32) * wj[:, None]
        if tp > 1:
            y = jax.lax.psum(y, "model")
        y = y.astype(dt)

        if shared:
            y = y + mlp_forward(shared, xt, "swiglu")
        return y.reshape(b_loc, s, d), aux

    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, w_spec, shared_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
        p.get("shared", {}),
    )
    return out, aux.astype(jnp.float32)
