"""Kernels (``kernels/paged_decode/kernel.py`` ``paged_gather_attend``):
roofline share of the kernel's trace events, summed over the chips.  The
work is that of the actual context lengths in every layer that holds
paged K/V (``paged_layers``), in the traced decode iterations
(``bench/work.py``), not of the pages the kernel walks.

The kernel has no name of its own in the compiled step (a ``closed_call``
custom call); it is the one that returns the three float32 partials
(acc [B, Hkv, G, D], m and l [B, Hkv, G, 1])."""
from bench import work

KERNEL = r"^custom-call \(f32\[[\d,]+\],f32\[[\d,]+,1\],f32\[[\d,]+,1\]\)$"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace.kernel_s(KERNEL) * run.n_chips
    layers = work.paged_layers(run.config)
    flops = nbytes = 0
    for s in run.traced_spans:
        for ctxs in s.ctxs:
            f, b = work.gather_attend_work(run.config, ctxs)
            flops += f * layers
            nbytes += b * layers
    if not device_s or not nbytes:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, run.peak) / device_s
