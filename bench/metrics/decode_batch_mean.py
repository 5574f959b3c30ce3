"""Engine decode (``serve/engine.py`` ``step``): sequences decoded per
decode iteration, the mean over the iterations of the benchmark's spans
around ``step`` in the window."""


def read(run):
    its = [len(c) for s in run.spans if s.name == "step" for c in s.ctxs]
    if not its:
        return None
    return sum(its) / len(its)
