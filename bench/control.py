"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3
                             [--fault half_batch|unchanged_state]

For each seed, in this one process: a run of the cell as the benchmark
makes it (its own window and load), then the comparison's readings: the
program's against the float32 reference (material for the lower
readings), and the control's, the same reference computed with float8
inputs in the program's place (the upper readings). ``--fault`` plants one
of ``faults.TRAIN`` under a training cell's timed path instead. Each
reading is held to the cell's limits as a run holds its own: every seed
prints one JSON line with the program's verdict (``correct``) and the
control's (``control_correct``), which has to come out false, and the
checks behind both go to standard error. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def report(seed: int, who: str, checks: dict) -> None:
    verdict = all(c["ok"] for c in checks.values())
    for name, c in checks.items():
        print(f"[check] seed {seed} {who} {name}: {c['value']!r} limit "
              f"{c['limit']!r} ({'ok' if c['ok'] else 'FAILED'})",
              file=sys.stderr)
    print(f"[check] seed {seed} {who}: correct={verdict}", file=sys.stderr,
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    spec = run.find_cell(run.load_json(run.ROOT, "BENCHMARK.json"),
                         args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax

    run.require_chips(jax, spec["cell"]["chips"])
    run.enable_cache(jax)
    import faults

    runner = importlib.import_module(run.RUNNERS[spec["traffic"]["kind"]])
    kw = {"control": True}
    if args.fault:
        kw = {"fault": faults.TRAIN[args.fault]}
    t = T_START
    for seed in args.seeds:
        res = runner.run(spec, seed=seed, seconds=args.seconds, trace=False,
                         t_start=t, **kw)
        line = {"seed": seed, **res["scored"], "correct": res["correct"],
                "end_to_end": res["end_to_end"], "checks": res["checks"]}
        report(seed, "program" if not args.fault else args.fault,
               res["checks"])
        if "control_checks" in res:
            line["control_correct"] = all(
                c["ok"] for c in res["control_checks"].values())
            line["control_checks"] = res["control_checks"]
            report(seed, "control", res["control_checks"])
        print(json.dumps(line), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
