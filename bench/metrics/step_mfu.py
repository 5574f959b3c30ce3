"""Model step (``models/transformer.py`` decode step): FLOPs the decoded
tokens of the traced stretch need (``bench/work.py``, one token per
prefill dispatch, one per active sequence per decode iteration), over the
traced stretch's length, the chips and their bf16 peak."""
from bench import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    flops = sum(work.step_flops(run.config, ctxs)
                for s in run.traced_spans for ctxs in s.ctxs)
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s * run.n_chips
                            * run.peak["bf16_flops_per_s"])
