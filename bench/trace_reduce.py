"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) has one
plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds every HLO
op the chip ran, control-flow ops (``while``, ``conditional``) spanning the
ops inside them; event names are the op's HLO text (``%flash_fwd.35 = ...
custom-call(...)``), so a Pallas kernel is found by its ``name=``.  The host
plane (``/host:CPU``) has a line per thread; the benchmark marks the
measured window there with a span named :data:`WINDOW_SPAN`.  Device and
host events share one clock.

Everything is clipped to the window:

* busy time of a chip: the union of its op intervals;
* a kernel's calls and device time: its op events, summed over chips;
* ``breakdown``: the device ops that took most time (control-flow containers
  left out, since they only hold other ops), and the longest idle gaps of
  chip 0, each labelled by the innermost host event that spans it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_OP_NAME = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)?(?: |$)")
CONTAINERS = ("while", "conditional", "cond", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Interval = Tuple[float, float]


def op_kind(event_name: str) -> str:
    """``%flash_fwd.35 = (...) custom-call(...)`` -> ``flash_fwd``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def _short(event_name: str, width: int = 90) -> str:
    """An op's HLO text without layouts, cut to ``width``."""
    return _LAYOUT.sub("", event_name)[:width].strip()


def union_length(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """The reduced trace: per chip, the op events inside the window."""

    def __init__(self, profile, n_chips: Optional[int] = None):
        planes = list(profile.planes)
        host = [p for p in planes if p.name == HOST_PLANE]
        spans = [(e.start_ns, e.end_ns) for p in host for line in p.lines
                 for e in line.events if e.name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span in the "
                             f"trace, found {len(spans)}")
        lo, hi = self.w0, self.w1 = spans[0]

        def inside(events):
            out = []
            for e in events:
                s, t = e.start_ns, e.end_ns
                if t > lo and s < hi:
                    out.append((max(s, lo), min(t, hi), e.name))
            return out

        self.ops: Dict[int, List[Tuple[float, float, str]]] = {}
        for p in planes:
            m = DEVICE_PLANE.match(p.name)
            if m and (n_chips is None or int(m.group(1)) < n_chips):
                for line in p.lines:
                    if line.name == OPS_LINE:
                        self.ops[int(m.group(1))] = inside(line.events)
        if not self.ops:
            raise ValueError("no TPU op events in the trace")
        self.host = [ev for p in host for line in p.lines
                     for ev in inside(line.events) if ev[2] != WINDOW_SPAN]

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(union_length([(s, e) for s, e, _ in ev])
                   for ev in self.ops.values()) * 1e-9 / len(self.ops)

    def kernel(self, name: str) -> Tuple[int, float]:
        """Calls of a kernel in the window and its device seconds (clipped to
        the window), summed over the chips."""
        calls, ns = 0, 0.0
        for ev in self.ops.values():
            for s, e, n in ev:
                if op_kind(n) == name:
                    calls += 1
                    ns += e - s
        return calls, ns * 1e-9

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        totals: Dict[str, float] = {}
        for ev in self.ops.values():
            for s, e, n in ev:
                if op_kind(n) in CONTAINERS:
                    continue
                key = _short(n)
                totals[key] = totals.get(key, 0.0) + (e - s)
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        chip0 = self.ops[min(self.ops)]
        idle = sorted(gaps([(s, e) for s, e, _ in chip0], self.w0, self.w1),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[self.host_label(s, e), (e - s) * 1e-9]
                              for s, e in idle]}

    def host_label(self, s: float, e: float) -> str:
        """The shortest host event that spans the gap ``[s, e]``, or failing
        that the one that overlaps it most."""
        spans = [(he - hs, n) for hs, he, n in self.host if hs <= s and he >= e]
        if spans:
            return min(spans)[1]
        best = max(((min(he, e) - max(hs, s), n) for hs, he, n in self.host
                    if he > s and hs < e), default=(0, "no host event"))
        return best[1]


def load(directory: str, n_chips: Optional[int] = None) -> Trace:
    import jax
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {directory}, "
                         f"found {files}")
    return Trace(jax.profiler.ProfileData.from_file(files[0]), n_chips)
