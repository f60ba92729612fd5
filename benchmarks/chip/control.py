"""The controls: each cell's plain reference put in the program's place in
a lower precision (counts in bfloat16, keys compared as bfloat16, CG vectors
in bfloat16), compared by the cell's own checks, which have to fail.

    python3 benchmarks/chip/control.py --workload <name> --seed <n> [--seed ...]

prints one JSON line per seed with each number compared and its limit. Run
it on the chip at the cell's size; the benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def readings(workload: str, seed: int, config_overrides: dict | None = None,
             spec: dict | None = None) -> dict:
    from benchmarks.chip import harness

    spec = harness.load_spec() if spec is None else spec
    cell = harness.Cell.find(spec, workload)
    cfg = {**cell.config, **(config_overrides or {})}
    job = harness.load_module("jobs", cell.traffic["job"]).Job(
        cfg, cell.traffic, seed, int(cell.workload["chips"]))
    checks = job.check([job.control_answer()])
    return {"workload": workload, "seed": seed,
            "correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit} for c in checks}}


def main(argv=None):
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path[0] = root
    sys.path.insert(1, os.path.join(root, "src"))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    for s in args.seed:
        print(json.dumps(readings(args.workload, s)), flush=True)


if __name__ == "__main__":
    main()
