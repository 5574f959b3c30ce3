"""Sharded attention (``parallel/paged_attention.py``): device time in
collective operations (the exchange between chips), over device busy time,
averaged over the chips."""


def read(run):
    if run.trace is None or run.n_chips < 2 or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.collective_s / run.trace.busy_s
