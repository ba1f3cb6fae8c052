import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
the production mesh with abstract (ShapeDtypeStruct) params/inputs — no
allocation — and record memory / cost / collective statistics for the
roofline analysis.

Usage:
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json and is skipped
if that file already exists (restartable sweep).
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPE_PRESETS, TrainConfig
from repro.configs.registry import ARCH_IDS, batch_specs, get_config
from repro.distributed.sharding import (
    apply_seq_sharding_config,
    named_sharding,
    shardings_for,
    sharding_rules,
)
from repro.launch.mesh import make_production_mesh
from repro.models.model import model_specs
from repro.models.params import abstract_params, count_params, logical_axes
from repro.optim.adamw import AdamWState
from repro.optim.schedules import warmup_cosine
from repro.train.train_step import make_serve_step, make_train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|u32|s8|u8|s16|u16|s64|u64|pred|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    """Total bytes of all shapes on an HLO op result (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective op stats from the post-SPMD HLO.

    For each collective line we record the RESULT bytes (per-device) and a
    modeled transmitted-bytes figure using ring-collective factors with the
    participant count parsed from replica_groups:
        all-gather:      out * (g-1)/g
        all-reduce:      out * 2(g-1)/g
        reduce-scatter:  out * (g-1)          (input = out*g)
        all-to-all:      out * (g-1)/g
        collective-permute: out
    """
    stats: dict = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        op = None
        for c in _COLLECTIVES:
            if re.search(rf"= \S+ {c}(-start)?\(", stripped):
                op = c
                break
        if op is None:
            continue
        out_bytes = _shape_bytes(stripped.split("=", 1)[1].split("(", 1)[0])
        g = 1
        m = _GROUPS_RE.search(stripped)
        if m:
            g = int(m.group(2))
        else:
            m = _GROUPS_LIST_RE.search(stripped)
            if m:
                g = len(m.group(1).split(","))
        if op == "all-gather":
            moved = out_bytes * (g - 1) / max(g, 1)
        elif op == "all-reduce":
            moved = out_bytes * 2 * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            moved = out_bytes * (g - 1)
        elif op == "all-to-all":
            moved = out_bytes * (g - 1) / max(g, 1)
        else:
            moved = out_bytes
        rec = stats.setdefault(op, {"count": 0, "result_bytes": 0, "moved_bytes": 0.0})
        rec["count"] += 1
        rec["result_bytes"] += out_bytes
        rec["moved_bytes"] += moved
    return stats


def _sharded_bytes(tree_abstract, tree_sharding, n_dev: int) -> float:
    """Analytic per-device bytes of a sharded abstract pytree."""
    total = 0.0
    for leaf, sh in zip(jax.tree.leaves(tree_abstract), jax.tree.leaves(
            tree_sharding, is_leaf=lambda x: isinstance(x, NamedSharding))):
        shard_shape = sh.shard_shape(leaf.shape)
        n = leaf.dtype.itemsize
        for d in shard_shape:
            n *= d
        total += n
    return total


def _probe_cfg(cfg, num_layers: int, seq_len: int):
    """Variant of ``cfg`` for HLO cost probing: unrolled layers AND unrolled
    inner chunk scans (chunked attention, mLSTM/mamba chunk scans) so XLA's
    cost_analysis — which counts while-loop bodies once — sees every body.

    Math-identical to the real program: the online-softmax / chunk recurrence
    structure is preserved, so FLOPs AND bytes reflect the streaming
    implementation (an earlier probe swapped chunked->full attention, which
    inflated HLO bytes with n^2 score materialization the real kernels never
    do — see EXPERIMENTS.md §Perf iteration 0)."""
    import dataclasses

    # Cap unrolled SSM chunk count at 64: mamba's per-chunk associative
    # scans make XLA compile time explode past ~100 unrolled bodies (hymba
    # prefill_32k never finished). Larger chunks mildly OVERestimate the
    # mLSTM/SSD intra-chunk terms (O(chunk) per token) — conservative for
    # the roofline.
    ssm_chunk = max(cfg.ssm_chunk, -(-seq_len // 64))
    return dataclasses.replace(
        cfg,
        num_layers=num_layers,
        scan_layers=False,
        unroll_scans=True,
        ssm_chunk=ssm_chunk,
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, attention: str | None = None,
             remat: str | None = None, extra_rules: dict | None = None,
             probe: bool = True, cfg_overrides: dict | None = None,
             tcfg: TrainConfig | None = None) -> dict:
    import dataclasses

    cfg = get_config(arch)
    if attention:
        field = ("decode_attention_impl"
                 if SHAPE_PRESETS[shape_name].kind == "decode" else "attention_impl")
        cfg = dataclasses.replace(cfg, **{field: attention})
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPE_PRESETS[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    overrides = dict(extra_rules or {})
    if cfg.num_heads % mesh.shape["model"] != 0 and shape.kind != "decode":
        # Heads don't divide the TP axis (28/25/56-head archs): shard the
        # sequence over "model" instead (context parallelism) so per-device
        # compute still scales 1/256; GSPMD inserts the K/V gathers.
        overrides.setdefault("seq", "model")
    if shape_name == "long_500k":
        # batch=1: sequence-parallel cache, batch unsharded.
        overrides.setdefault("cache_batch", None)
        overrides.setdefault("batch", None)
        overrides.setdefault(
            "cache_seq", ("pod", "data", "model") if multi_pod else ("data", "model")
        )
    elif shape.kind == "decode":
        # Shard the KV-cache sequence over "model" (kv heads are often
        # narrower than the model axis).
        overrides.setdefault("cache_seq", "model")

    # Seq-sharded fused cells keep attention_backend intact and lower the
    # shard_map context-parallel program (kernels/sharded.py), so the
    # compile-time stats below model the same kernel route the trainer runs.
    cfg = apply_seq_sharding_config(cfg, mesh, overrides)

    t0 = time.time()
    result: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "devices": n_dev,
        "attention": (cfg.decode_attention_impl if shape.kind == "decode"
                       else cfg.attention_impl),
        "remat": cfg.remat,
    }
    result.update(_lower_and_stats(cfg, shape, mesh, overrides, tcfg))

    # HLO-cost probe: unrolled L=2 / L=4 variants -> per-layer-linear
    # extrapolation of flops / bytes / collective traffic (XLA cost_analysis
    # counts while-loop bodies once; DESIGN.md §7).
    if probe and cfg.scan_layers and cfg.num_layers > 4:
        try:
            p2 = _lower_and_stats(_probe_cfg(cfg, 2, shape.seq_len), shape, mesh, overrides, tcfg)
            p4 = _lower_and_stats(_probe_cfg(cfg, 4, shape.seq_len), shape, mesh, overrides, tcfg)
            L = cfg.num_layers
            lin = lambda a, b: a + (b - a) / 2.0 * (L - 2)
            result["probe"] = {
                "flops_l2": p2["flops_total"], "flops_l4": p4["flops_total"],
                "flops_extrapolated": lin(p2["flops_total"], p4["flops_total"]),
                "bytes_extrapolated": lin(
                    p2["hlo_bytes_accessed"], p4["hlo_bytes_accessed"]
                ),
                "collective_moved_extrapolated": lin(
                    _moved(p2["collectives"]), _moved(p4["collectives"])
                ),
                "collectives_l4": p4["collectives"],
            }
        except Exception:
            result["probe"] = {"error": traceback.format_exc()}

    result["total_s"] = round(time.time() - t0, 2)
    return result


def _moved(collectives: dict) -> float:
    return sum(v["moved_bytes"] for v in collectives.values())


def _lower_and_stats(cfg, shape, mesh, overrides, tcfg=None) -> dict:
    """Lower + compile one step function; return cost/memory/collective stats."""
    n_dev = mesh.size
    result: dict = {}
    t0 = time.time()
    specs = model_specs(cfg)
    result["param_count"] = count_params(specs)
    pdt = jnp.dtype(cfg.param_dtype)
    params_abs = abstract_params(specs, dtype=pdt)
    axes = logical_axes(specs)

    with mesh, sharding_rules(mesh, overrides):
        p_sh = shardings_for(mesh, axes, params_abs)
        bspecs, baxes = batch_specs(cfg, shape)
        b_sh = shardings_for(mesh, baxes, bspecs)

        if shape.kind == "train":
            tcfg = tcfg or TrainConfig()
            lr_fn = warmup_cosine(3e-4, 100, 1000)
            step_fn = make_train_step(cfg, tcfg, lr_fn)
            odt = jnp.dtype(tcfg.opt_state_dtype)
            opt_abs = AdamWState(
                step=jax.ShapeDtypeStruct((), jnp.int32),
                m=abstract_params(specs, dtype=odt),
                v=abstract_params(specs, dtype=odt),
            )
            o_sh = AdamWState(step=NamedSharding(mesh, P()), m=p_sh, v=p_sh)
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_abs, opt_abs, bspecs)
            state_bytes = (
                _sharded_bytes(params_abs, p_sh, n_dev)
                + 2 * _sharded_bytes(opt_abs.m, p_sh, n_dev)
            )
        elif shape.kind == "prefill":
            from repro.distributed.sharding import spec_for
            from repro.train.train_step import make_prefill_step

            step_fn = make_prefill_step(cfg)
            # Keep logits vocab-TP-sharded on the way out: leaving the output
            # sharding open makes GSPMD replicate the (d, V) unembed table
            # on every chip (measured 2.2GB/step, §Perf it5).
            logits_sh = NamedSharding(mesh, spec_for(("batch", None, "vocab_act")))
            jitted = jax.jit(step_fn, in_shardings=(p_sh, b_sh),
                             out_shardings=logits_sh)
            lowered = jitted.lower(params_abs, bspecs)
            state_bytes = _sharded_bytes(params_abs, p_sh, n_dev)
        else:  # decode
            step_fn = make_serve_step(cfg)
            cache_abs, tok_abs = bspecs["cache"], bspecs["tokens"]
            c_sh, t_sh = b_sh["cache"], b_sh["tokens"]
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, c_sh, t_sh),
                out_shardings=(NamedSharding(mesh, P()), c_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_abs, cache_abs, tok_abs)
            state_bytes = (
                _sharded_bytes(params_abs, p_sh, n_dev)
                + _sharded_bytes(cache_abs, c_sh, n_dev)
            )

        result["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 2)

        cost = compiled.cost_analysis() or {}
        result["flops_total"] = float(cost.get("flops", 0.0))
        result["hlo_bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        try:
            mem = compiled.memory_analysis()
            result["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            }
        except Exception as e:  # CPU backend may not implement it
            result["memory_analysis"] = {"error": str(e)}
        result["state_bytes_per_device"] = state_bytes
        hlo = compiled.as_text()
        result["collectives"] = parse_collectives(hlo)
        result["hlo_lines"] = hlo.count("\n")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ["paper-bert"])
    ap.add_argument("--shape", choices=list(SHAPE_PRESETS))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--attention", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for experiment variants")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_IDS if args.all else [args.arch]
    shapes = list(SHAPE_PRESETS) if args.all else [args.shape]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    for arch, shape, mesh_kind in cells:
        tag = f"__{args.tag}" if args.tag else ""
        path = os.path.join(args.out, f"{arch}__{shape}__{mesh_kind}{tag}.json")
        if os.path.exists(path) and not args.force:
            print(f"[skip] {path}")
            continue
        print(f"[run ] {arch} x {shape} x {mesh_kind} ...", flush=True)
        try:
            res = run_cell(
                arch, shape, mesh_kind == "multi",
                attention=args.attention, remat=args.remat,
                probe=(mesh_kind == "single"),  # roofline table is single-pod
            )
            res["status"] = "ok"
        except Exception:
            res = {
                "arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "error", "traceback": traceback.format_exc(),
            }
            print(res["traceback"])
        with open(path, "w") as f:
            json.dump(res, f, indent=2)
        print(f"[done] {path}: {res.get('status')} "
              f"compile={res.get('compile_s')}s flops={res.get('flops_total', 0):.3e}",
              flush=True)


if __name__ == "__main__":
    main()
