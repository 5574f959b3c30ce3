"""Chip benchmark of the pooled emulated-memory serving path.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

One run is one process: it builds the cell's engine, warms it up (set-up),
measures for ``--seconds``, checks what it served against the plain
reference, and prints one JSON line as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks`` (each compared number with
its limit, also printed as the last lines of standard error).

It runs on TPU only: with no TPU, or fewer chips than the cell asks for,
it exits non-zero and prints no result.  JAX's compilation cache lives in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.chip_cell(args.workload)
    if cell is None:
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), PROCESS_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
