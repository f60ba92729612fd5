#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout, on a machine
with the cell's chips:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of stdout is the result's JSON object. Without a TPU, or with
fewer chips than the cell asks for, it exits with code 3 and prints no
result. JAX's compilation cache is kept in ``<checkout>/.jax_cache``, every
program included, so that only a cell's first run in a checkout compiles.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    sys.path[0] = ROOT  # in place of this script's directory
    sys.path.insert(1, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    from benchmarks.chip import harness

    try:
        harness.run(sys.argv[1:], t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(3)


if __name__ == "__main__":
    main()
