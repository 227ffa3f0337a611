"""The program's host spans (``src/repro/tracing.py``) laid over the chips'
idle time, for the host-controller metrics.

The program marks its host work with ``jax.profiler.TraceAnnotation`` spans
named ``/repro/<layer>/<what>``, so they sit in the profiler trace on the
device's clock: ``trace_reduce.Trace.host`` holds them, clipped to the
window, beside the runtime's own events.  A span's name may carry its
arguments as ``name#k=v#``; everything from the first ``#`` on is dropped.

Each chip's idle time is the gaps between its ops in the window
(``trace_reduce.gaps``); a reading is averaged over the chips, as
``device_idle_share.train`` is.  A program older than ``repro.tracing`` has
no spans to read: every reader then returns ``None``.
"""
from __future__ import annotations

import importlib.util
from typing import Callable, List

import trace_reduce
from trace_reduce import Interval

PREFIX = "/repro/"


def instrumented() -> bool:
    """Whether the program under test marks its host work with spans."""
    return importlib.util.find_spec("repro.tracing") is not None


def span_name(event_name: str) -> str:
    """``/repro/train/drain#step=8#`` -> ``/repro/train/drain``."""
    return event_name.split("#", 1)[0]


def spans(tr, match: Callable[[str], bool]) -> List[Interval]:
    """The window-clipped intervals of the host spans whose name matches,
    on every thread."""
    return [(s, e) for s, e, n in tr.host if match(span_name(n))]


def merged(xs: List[Interval]) -> List[List[float]]:
    """The union of ``xs`` as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(xs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of (union of ``a``) intersected with (union of ``b``)."""
    total, a, b = 0.0, merged(a), merged(b)
    i = j = 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle(tr) -> List[List[Interval]]:
    """Each chip's idle gaps in the window."""
    return [trace_reduce.gaps([(s, e) for s, e, _ in ev], tr.w0, tr.w1)
            for ev in tr.ops.values()]


def idle_share_under(tr, match: Callable[[str], bool]) -> float:
    """Percent of the window in which a chip was idle while a matching span
    was open, averaged over the chips."""
    under = spans(tr, match)
    per_chip = [overlap(g, under) for g in idle(tr)]
    return 100.0 * sum(per_chip) / len(per_chip) / (tr.w1 - tr.w0)


def uncovered(a: List[Interval], b: List[Interval]) -> float:
    """Length of (union of ``a``) that (union of ``b``) leaves uncovered."""
    total, b, j = 0.0, merged(b), 0
    for s, e in merged(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while s < e and k < len(b) and b[k][0] < e:
            total += max(0.0, b[k][0] - s)
            s = max(s, b[k][1])
            k += 1
        total += max(0.0, e - s)
    return total


def idle_share_outside(tr, match: Callable[[str], bool]) -> float:
    """Percent of the window in which a chip was idle while no matching span
    was open, averaged over the chips."""
    under = spans(tr, match)
    per_chip = [uncovered(g, under) for g in idle(tr)]
    return 100.0 * sum(per_chip) / len(per_chip) / (tr.w1 - tr.w0)


def share_inside(tr, match: Callable[[str], bool]) -> float:
    """Percent of the window inside a matching span."""
    return 100.0 * trace_reduce.union_length(spans(tr, match)) \
        / (tr.w1 - tr.w0)


def named(name: str) -> Callable[[str], bool]:
    return lambda n: n == name


def ours(n: str) -> bool:
    return n.startswith(PREFIX)


def readable(ctx) -> bool:
    tr = ctx["trace"]
    return tr is not None and tr.w1 > tr.w0 and instrumented()
