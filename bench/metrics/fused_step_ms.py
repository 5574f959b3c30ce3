"""Engine decode (``serve/engine.py`` ``step``, ``serve/fused_decode.py``):
host milliseconds of the benchmark's span around ``step``, per decode step
it returned."""


def read(run):
    spans = [s for s in run.spans if s.name == "step"]
    steps = sum(len(s.ctxs) for s in spans)
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / steps
