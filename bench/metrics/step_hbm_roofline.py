"""Model step (``models/transformer.py`` decode step): the least time the
necessary bytes of the traced decode iterations take at peak HBM
bandwidth (the weights once per iteration, the K/V of the actual context
lengths, one new row per sequence; ``bench/work.py``), over the device time
of the step programs (``sampled_decode_step``, ``fused_decode_run``)
summed over the chips."""
from bench import work

PROGRAMS = ("sampled_decode_step", "fused_decode_run")


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace.program_s(PROGRAMS) * run.n_chips
    nbytes = sum(work.step_bytes(run.config, ctxs)
                 for s in run.traced_spans for ctxs in s.ctxs)
    if not device_s or not nbytes:
        return None
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / device_s
