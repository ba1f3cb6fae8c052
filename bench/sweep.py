"""Find an open-loop cell's knee once, on the chip: the highest arrival
rate the server sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 1 2 3 4

One engine, set up once; for each rate in turn the cell's traffic mix at
that rate for one window, then a drain. Per rate it prints the time to
first token (median and p90 over the requests due in the window), how the
wait grows across the window (the p50 over its last third against its
first third: near 1 below the knee, growing above it), and output tokens
per second. The cell's traffic file then states a rate at about four
fifths of the knee; the benchmark's runs never search for one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = run.find_cell(run.load_json(run.ROOT, "BENCHMARK.json"),
                         args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    import jax.numpy as jnp

    run.require_chips(jax, spec["cell"]["chips"])
    run.enable_cache(jax)
    import serve_cell
    import traffic_gen
    import weights
    from repro.models.model import model_specs
    from repro.models.params import abstract_params
    from repro.serve.engine import ServeEngine
    from repro.telemetry import Telemetry

    config, traffic = spec["config"], spec["traffic"]
    cfg, serve = serve_cell.build(config)
    params = weights.make_weights(
        abstract_params(model_specs(cfg), jnp.dtype(cfg.param_dtype)),
        args.seed, cfg.num_layers, jnp.dtype(cfg.param_dtype))
    engine = ServeEngine(cfg, params, serve=serve,
                         telemetry=Telemetry(enabled=True))
    serve_cell.warm_up(engine, traffic, serve.max_seq, args.seed,
                       cfg.vocab_size)
    print(f"set-up {time.perf_counter() - T_START:.1f}s", flush=True)
    for k, rate in enumerate(args.rates):
        mix = dict(traffic, rate_per_s=rate)
        reqs = [dataclasses.replace(r, index=r.index + (k + 1) * 100_000)
                for r in traffic_gen.schedule(mix, args.seed + k,
                                              cfg.vocab_size, args.seconds)]
        stamps = serve_cell.Stamps()
        t0, t_end, due, sent = serve_cell.drive(
            engine, reqs, mix, args.seconds, stamps, serve.max_lanes)
        engine.run()
        e2e = serve_cell.end_to_end("open_loop", reqs, t0, t_end, due,
                                    stamps, args.seconds)
        ttft = sorted((due[u], stamps.times[u][0] - due[u])
                      for u in due if due[u] < t_end and u in stamps.times)
        third = max(len(ttft) // 3, 1)
        first = np.median([w for _, w in ttft[:third]])
        last = np.median([w for _, w in ttft[-third:]])
        print(json.dumps({
            "rate_per_s": rate, "due": e2e["ttft_samples"],
            "ttft_p50_ms": 1e3 * float(np.median([w for _, w in ttft])),
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "growth_last_over_first": float(last / first),
            "itl_p95_ms": e2e["itl_p95_ms"],
            "output_tokens_per_s": e2e["output_tokens_per_s"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
