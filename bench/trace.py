"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The reader (:func:`read_xplane`) turns the profiler's ``.xplane.pb`` into
plain tuples: for each device, its operations and its program (module)
executions as ``(start_ns, end_ns, label)``, and the benchmark's host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) on the same clock.
Everything after that (:func:`reduce`) is arithmetic on those tuples, so
the tests check it on traces built by hand.

- Busy time is the union of a device's operation intervals inside the
  window; the idle share is 1 minus busy over the window.  Both are
  averaged over the devices.
- An operation's label is its HLO instruction name without the instance
  number and, for a custom call, its output types: the kernels of the
  fused step are ``closed_call`` custom calls, told apart by what they
  return.  Control flow (``while``, ``conditional``, ``call``) holds other
  operations, so it counts towards busy time and not towards op time.
- Kernel time is the summed duration of the operations whose label matches
  the kernel's pattern; collective time likewise.
- Every idle gap of a device is attributed to the innermost benchmark span
  that holds the gap's midpoint ("outside spans" when none does).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

#: operation labels that are collectives (the exchange between chips)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|pmax|send|recv", re.IGNORECASE)
SPAN_PREFIX = "bench."
OUTSIDE = "outside spans"


@dataclasses.dataclass
class Device:
    name: str
    ops: list            # [(start_ns, end_ns, label)]
    modules: list        # [(start_ns, end_ns, program name)]


@dataclasses.dataclass
class Summary:
    """What the metric readers take from a trace."""
    window_s: float
    n_devices: int
    busy_s: float                      # mean over devices
    op_s: dict                         # label -> seconds, mean over devices
    module_s: dict                     # program -> seconds, mean over devices
    idle_s: dict                       # host span -> idle seconds, mean
    collective_s: float                # mean over devices

    def kernel_s(self, pattern: str) -> float:
        """Device seconds (mean over devices) of ops whose label matches
        the regular expression ``pattern``."""
        return sum(s for k, s in self.op_s.items() if re.search(pattern, k))

    def program_s(self, names) -> float:
        """Device seconds (mean over devices) of the named programs."""
        return sum(s for k, s in self.module_s.items()
                   if any(n in k for n in names))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def clip(intervals, lo: int, hi: int) -> list:
    """Intervals cut to ``[lo, hi]``; empty ones dropped."""
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, *rest))
    return out


def union(intervals) -> list:
    """Merged ``(start, end)`` pairs covering the intervals."""
    merged: list = []
    for s, e, *_ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, lo: int, hi: int) -> list:
    """``(start, end)`` stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, t: float) -> str:
    """Name of the shortest span holding time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else OUTSIDE


def reduce(devices: list, spans: list, window: tuple) -> Summary:
    """Reduce device events and host spans over ``window`` (ns)."""
    lo, hi = window
    if hi <= lo or not devices:
        raise ValueError(f"empty trace window {window} or no devices")
    n = len(devices)
    op_s: dict = collections.defaultdict(float)
    module_s: dict = collections.defaultdict(float)
    idle_s: dict = collections.defaultdict(float)
    busy = coll = 0.0
    spans = sorted(spans)
    for dev in devices:
        ops = clip(dev.ops, lo, hi)
        busy += busy_ns(ops)
        for s, e, label in ops:
            if label.split(" ")[0] in CONTAINERS:
                continue
            op_s[label] += (e - s) / 1e9 / n
            if COLLECTIVE.search(label):
                coll += e - s
        for s, e, name in clip(dev.modules, lo, hi):
            module_s[name] += (e - s) / 1e9 / n
        for s, e in gaps(ops, lo, hi):
            idle_s[innermost(spans, (s + e) / 2)] += (e - s) / 1e9 / n
    return Summary(window_s=(hi - lo) / 1e9, n_devices=n,
                   busy_s=busy / 1e9 / n, op_s=dict(op_s),
                   module_s=dict(module_s), idle_s=dict(idle_s),
                   collective_s=coll / 1e9 / n)


_SUFFIX = re.compile(r"[.:]\d+$|\(\d+\)$")
#: ``%name.N = <output types> opcode(...`` of an HLO instruction, as the TPU
#: trace names its operations
_HLO = re.compile(r"^%(?P<name>[\w.\-]+) = (?P<types>.*?) "
                  r"(?P<opcode>[a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
#: operations that hold other operations
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """An operation's label: its name without the instance number
    (``fusion.12`` -> ``fusion``); for an HLO instruction text, the
    instruction's name, or ``custom-call`` and its output types without
    layouts (``custom-call (f32[16,8,2,128],f32[16,8,2,1])``); a control-
    flow instruction by its opcode (``while``)."""
    m = _HLO.match(name)
    if not m:
        return _SUFFIX.sub("", name)
    if m["opcode"] in CONTAINERS:
        return m["opcode"]
    if m["opcode"] == "custom-call":
        types = _LAYOUT.sub("", m["types"]).replace(" ", "")
        return f"custom-call {types}"
    return _SUFFIX.sub("", m["name"])


def read_xplane(log_dir: str, device_prefix: str = "/device:TPU:"):
    """(devices, bench spans) from the trace under ``log_dir``.  Device
    planes are those named ``device_prefix<n>``; their ``XLA Ops`` line
    gives the operations and their ``XLA Modules`` line the programs."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane.pb under {log_dir}, "
                                f"found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns, op_label(e.name))
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.start_ns, e.end_ns, op_label(e.name))
                                for e in line.events]
            devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name[len(SPAN_PREFIX):])
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.name)
    return devices, spans
