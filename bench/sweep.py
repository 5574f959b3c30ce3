"""The knee of an open-loop cell: its mix at several rates.

  python bench/sweep.py --workload <name> --seconds <s> --seed <n> \
      --rates 0.2 0.3 0.4

Runs the cell once per rate, in one process, each as ``bench/run.py``
runs it (without a trace) but with the mix's ``rate_per_s`` replaced, and
prints one JSON line per rate: the backlog at the window's open and close
(requests due and not yet admitted, and requests in slots), the mean
time to first token of the requests due in the first and in the second
half of the window, the requests finished in the window, and the
end-to-end metrics.  The knee is the highest rate whose backlog does not
grow over the window; a cell's mix offers 0.8 of it.  TPU only.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.chip_cell(args.workload)
    if cell is None:
        return 2
    if cell.mix["loop"] != "open":
        print(f"sweep: {cell.name} is not an open loop", file=sys.stderr)
        return 2
    for i, rate in enumerate(args.rates):
        start = PROCESS_START if i == 0 else time.perf_counter()
        swept = harness.Cell(cell.name, cell.chips, cell.config,
                             {**cell.mix, "rate_per_s": rate},
                             cell.end_to_end, cell.per_layer)
        r = harness.run_cell(swept, args.seed, args.seconds, False, start,
                             detail=True)
        d = r["detail"]
        print(json.dumps({
            "rate_per_s": rate, "attempted": r["attempted"],
            "finished": d["finished"], "at_open": d["at_open"],
            "at_close": d["at_close"],
            "mean_ttft_halves_s": d["mean_ttft_halves"],
            "metrics": d["e2e"], "correct": r["correct"],
            "shared_prompt_tokens": d["counters"].get(
                "shared_prompt_tokens")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
