"""Serving launcher: continuous-batching engine demo on a reduced model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
        --requests 12 --lanes 4 --max-seq 192

Loads (or randomly initializes) a reduced config, submits a synthetic
request stream and drives the engine to completion, printing throughput.
The decode path is the paper's spectral-shifting attention with the
incrementally-maintained landmark state (serve/decode.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import reduced
from repro.configs.registry import ARCH_IDS, get_config
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.runtime import enable_compile_cache
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=192)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    if cfg.family == "audio":
        raise SystemExit("whisper serving needs encoder features; use examples/")
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(args.seed))

    engine = ServeEngine(
        cfg, params, max_lanes=args.lanes, max_seq=args.max_seq, seed=args.seed
    )
    rng = np.random.default_rng(args.seed)
    for uid in range(args.requests):
        prompt = rng.integers(3, cfg.vocab_size, size=args.prompt_len).tolist()
        engine.submit(
            Request(uid, prompt, max_new_tokens=args.max_new,
                    temperature=args.temperature)
        )

    t0 = time.time()
    outputs = engine.run()
    dt = time.time() - t0
    total_new = sum(len(v) for v in outputs.values())
    print(
        f"[serve] {args.arch}: {len(outputs)}/{args.requests} requests, "
        f"{total_new} tokens in {dt:.2f}s "
        f"({total_new / max(dt, 1e-9):.1f} tok/s, lanes={args.lanes})"
    )
    for uid in sorted(outputs)[:3]:
        print(f"  req {uid}: {outputs[uid][:12]}...")
    return outputs


if __name__ == "__main__":
    main()
