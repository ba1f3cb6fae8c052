"""Chip benchmark: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout: a configuration (``bench/configs/<config>.json``) under a
traffic mix (``bench/traffic/<traffic>.json``). The traffic file's
``kind`` picks the runner (``backlog`` and ``open_loop``: ``serve_cell``;
``train``: ``train_cell``). With ``--trace 0`` the run reports the cell's
end-to-end metrics; with ``--trace 1`` it profiles the window and reports
its per-layer metrics, each read by ``bench/metrics/<metric>.py``. A new
cell, configuration or metric is new files and entries, never an edit.

The run refuses (a non-zero exit, no result line) when JAX finds no TPU, or fewer
chips than the cell asks for. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, a
traced run's ``breakdown``, and last ``checks``, the numbers that decided
``correct`` beside their limits (also the last lines of standard error).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
RUNNERS = {"backlog": "serve_cell", "open_loop": "serve_cell",
           "train": "train_cell"}


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str, base: str = BENCH) -> dict:
    """The cell's entry, its configuration and traffic files, and the
    metrics it reports: end-to-end ones listing it (or listing no cells),
    per-layer ones listing it (or, listing none, moving a metric it
    reports)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    cell = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in e2e_names
                              else [])]
    return {
        "cell": cell,
        "config": load_json(base, "configs", cell["config"] + ".json"),
        "traffic": load_json(base, "traffic", cell["traffic"] + ".json"),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def load_reader(metric: str, base: str = BENCH):
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = os.path.join(base, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache(jax) -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), with every program
    kept, small ones too, so that only a checkout's first run compiles."""
    from repro.runtime import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_layers(spec: dict, ctx: dict, base: str = BENCH) -> dict:
    out = {}
    for m in spec["per_layer"]:
        value = load_reader(m["name"], base)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be >= 0")

    spec = find_cell(load_json(ROOT, "BENCHMARK.json"), args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    device = require_chips(jax, spec["cell"]["chips"])
    print(f"[bench] compile cache {enable_cache(jax)}", file=sys.stderr,
          flush=True)
    kind = spec["traffic"]["kind"]
    if kind not in RUNNERS:
        raise SystemExit(f"bench: unknown traffic kind {kind!r}")
    runner = importlib.import_module(RUNNERS[kind])
    res = runner.run(spec, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START)

    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        line["metrics"] = read_layers(spec, res["ctx"])
        device["busy_s"] = res["ctx"]["trace"].busy_s
        device["window_s"] = res["ctx"]["trace"].window_s
        line["device"] = device
        line["breakdown"] = res["ctx"]["trace"].breakdown()
        print(f"[bench] kernel seconds by source in the traced "
              f"{device['window_s']:.3f}s: {res['ctx']['trace'].kernel_table()}",
              file=sys.stderr)
    else:
        wanted = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        line["metrics"] = {k: {"value": v, "unit": wanted[k]}
                           for k, v in res["end_to_end"].items()
                           if k in wanted}
        line["device"] = device
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"[check] {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
