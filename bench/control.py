"""Readings that set a cell's limit: the program's and the control's.

  python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3

Runs the cell once per seed, in one process, each as ``bench/run.py``
runs it (without a trace), but with the control in the program's place in
the check: the float8 reference's first pick at each position of the same
prompts and served tokens (``bench/reference.py``).  ``correct`` is then
the control's verdict, and comes out false where the limit separates the
two.  Prints one JSON line per seed: the control's gap and verdict, and
the program's own gap on the same sample.  The limit in the configuration
file lies between the largest program reading and the smallest control
reading.  The benchmark's own runs never run the control.  TPU only, like
the command.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.chip_cell(args.workload)
    if cell is None:
        return 2
    for i, seed in enumerate(args.seeds):
        start = PROCESS_START if i == 0 else time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, start,
                             control=True, detail=True)
        d = r["detail"]
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "control_gap": r["checks"]["max_logit_gap"]["value"],
            "limit": r["checks"]["max_logit_gap"]["limit"],
            "program_gap": d["program_gap"],
            "checked_requests": d["checked_requests"],
            "checked_tokens": d["checked_tokens"],
            "attempted": r["attempted"], "finished": d["finished"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
