"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --steps 50 --batch 8 --seq 256

On this CPU container ``--reduced`` shrinks the arch to smoke scale and runs
on a local mesh; on a real cluster the same entry point builds the
production mesh (``--mesh prod`` / ``--mesh prod-multipod``) and every step
function, sharding rule and checkpoint path is identical — the dry-run
(launch/dryrun.py) proves those configurations compile for every assigned
(arch × shape) cell.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging

import jax

from repro.configs.base import SHAPE_PRESETS, ShapeConfig, TrainConfig, reduced
from repro.configs.registry import ARCH_IDS, get_config
from repro.distributed.fault_tolerance import FailureInjector
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.runtime import enable_compile_cache
from repro.train.trainer import Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=ARCH_IDS + ["paper-bert"])
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPE_PRESETS))
    ap.add_argument("--reduced", action="store_true",
                    help="shrink to smoke scale (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=0, help="override global batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq len")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--attention", default=None,
                    help="override training attention impl")
    ap.add_argument("--mesh", default="local", choices=["local", "prod", "prod-multipod"])
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a simulated host failure at this step")
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.attention:
        cfg = dataclasses.replace(cfg, attention_impl=args.attention)

    preset = SHAPE_PRESETS[args.shape]
    shape = ShapeConfig(
        name=preset.name,
        seq_len=args.seq or preset.seq_len,
        global_batch=args.batch or preset.global_batch,
        kind="train",
    )
    tcfg = TrainConfig(
        learning_rate=args.lr,
        total_steps=max(args.steps, 10),
        warmup_steps=max(args.steps // 10, 1),
        microbatches=args.microbatches,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
    )
    if args.mesh == "local":
        mesh = make_local_mesh(args.model_parallel)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multipod")

    injector = (
        FailureInjector({args.fail_at: ["host0"]}) if args.fail_at else None
    )
    trainer = Trainer(cfg, tcfg, shape, mesh, injector=injector)
    history = trainer.run(args.steps)
    trainer.save(blocking=True)

    first, last = history[0], history[-1]
    print(
        f"[train] {args.arch} steps={len(history)} "
        f"loss {first['loss']:.4f} -> {last['loss']:.4f} "
        f"(mean step {sum(h['step_time_s'] for h in history)/len(history):.3f}s)"
    )
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=2)
    return history


if __name__ == "__main__":
    main()
