"""Kernels (``kernels/paged_decode/kernel.py`` ``paged_kv_write``):
roofline share of the kernel's trace events, summed over the chips.  The
work is one K row and one V row per sequence decoded, per layer that
holds paged K/V (``paged_layers``), in the traced decode iterations
(``bench/work.py``), not the pages it moves.

The kernel has no name of its own in the compiled step (a ``closed_call``
custom call); it is the one that returns two page arrays of one shape
(K and V pages, [n_pages, Hkv, slots, D])."""
from bench import work

KERNEL = r"^custom-call \((\w+)\[(\d+,\d+,\d+,\d+)\],\1\[\2\]\)$"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace.kernel_s(KERNEL) * run.n_chips
    layers = work.paged_layers(run.config)
    nbytes = sum(work.kv_write_work(run.config, len(ctxs))[1] * layers
                 for s in run.traced_spans for ctxs in s.ctxs)
    if not device_s or not nbytes:
        return None
    return 100.0 * work.roofline_s(0, nbytes, run.peak) / device_s
