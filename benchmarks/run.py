"""Benchmark harness entry point: one module per paper table/figure plus the
roofline table. Prints ``name,case,metric,value`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [SUITE ...] [--smoke]

Every bench that keeps a machine-readable trajectory routes its artifact
through :func:`write_bench`, so all of them share one envelope::

    BENCH_<name>.json = {bench, schema, shape?, host, provenance, cells}

``provenance`` (git SHA, jax version) makes artifacts correlatable across
commits; ``benchmarks/regress.py`` diffs the working-tree envelopes against
the ones committed at HEAD and fails on regressions beyond per-metric
tolerance bands. ``--smoke`` selects each suite's reduced cell grid (the
same cells CI's perf-regress job runs), equivalent to REPRO_BENCH_SMOKE=1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import (
    bench_accuracy,
    bench_complexity,
    bench_decode,
    bench_drift,
    bench_error_bound,
    bench_serve,
    bench_sharded_attn,
    bench_spectrum,
    bench_train_step,
    roofline,
)
from repro.runtime import enable_compile_cache

SUITES = {
    "complexity": bench_complexity.run,      # paper Table 1
    "spectrum": bench_spectrum.run,          # paper Figure 2
    "accuracy": bench_accuracy.run,          # paper Theorem 1
    "error_bound": bench_error_bound.run,    # paper §7 eq. (12)
    "roofline": roofline.run,                # EXPERIMENTS.md §Roofline
    "serve": bench_serve.run,                # paged vs dense serving TTFT
    "decode": bench_decode.run,              # streaming/gather/paged decode
                                             # (also writes BENCH_decode.json)
    "drift": bench_drift.run,                # frozen-mode drift decomposition
    "train_step": bench_train_step.run,      # fused vs jnp fwd+bwd
    "sharded_attn": bench_sharded_attn.run,  # context-parallel fused vs jnp
}

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def write_bench(
    name: str,
    *,
    schema: str,
    cells,
    shape: dict | None = None,
    extra: dict | None = None,
    results_copy: str | None = None,
) -> str:
    """The one writer every bench's JSON artifact goes through.

    Emits ``BENCH_<name>.json`` at the repo top level with the shared
    envelope (``bench``/``schema``/``shape``/``host``/``provenance``/
    ``cells``) that ``benchmarks/regress.py`` understands, and optionally a
    byte-identical ``results/<results_copy>`` back-compat copy (for benches
    that historically wrote under ``results/``). ``cells`` is normally a
    ``{cell_name: {metric: value}}`` dict (sorted for stable diffs); list
    cells (remat_study) pass through untouched but are invisible to the
    regression gate. Returns the top-level path."""
    import jax

    from repro.telemetry.provenance import provenance

    payload: dict = {"bench": name, "schema": schema}
    if shape is not None:
        payload["shape"] = shape
    if extra:
        payload.update(extra)
    payload["host"] = jax.default_backend()
    payload["provenance"] = provenance()
    payload["cells"] = (
        dict(sorted(cells.items())) if isinstance(cells, dict) else cells
    )
    blob = json.dumps(payload, indent=2) + "\n"
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        f.write(blob)
    if results_copy:
        rp = os.path.join(REPO_ROOT, "results", results_copy)
        os.makedirs(os.path.dirname(rp), exist_ok=True)
        with open(rp, "w") as f:
            f.write(blob)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*", metavar="SUITE",
                    help=f"suites to run (default: all of {list(SUITES)})")
    ap.add_argument("--only", default=None, choices=list(SUITES),
                    help="legacy spelling of a single positional suite")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced cell grids (same as REPRO_BENCH_SMOKE=1)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    selected = set(args.suites)
    if args.only:
        selected.add(args.only)
    unknown = selected - set(SUITES)
    if unknown:
        ap.error(f"unknown suite(s) {sorted(unknown)}; pick from {list(SUITES)}")

    rows: list[str] = []
    failures = 0
    for name, fn in SUITES.items():
        if selected and name not in selected:
            continue
        t0 = time.time()
        try:
            fn(rows)
            rows.append(f"suite,{name},elapsed_s,{time.time() - t0:.1f}")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            rows.append(f"suite,{name},ERROR,{type(e).__name__}: {e}")
    print("name,case,metric,value")
    print("\n".join(rows))
    if failures:
        print(f"# {failures} suite(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
