"""Engine admission and prefill (``serve/engine.py`` ``admit``): host
milliseconds of the benchmark's span around ``admit``, per prompt token it
actually prefilled (prompt minus the tokens shared from earlier pages)."""


def read(run):
    spans = [s for s in run.spans if s.name == "admit"]
    tokens = sum(len(s.ctxs) for s in spans)
    if not tokens:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / tokens
