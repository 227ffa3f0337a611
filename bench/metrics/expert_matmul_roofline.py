"""Share of their roofline that the held experts' grouped matmuls reach in
the window: the FLOPs they require over the bf16 peak, over the device time
of the grouped-matmul kernels.

The work follows the routing, which only the run knows: at each drain the
program's trainer marks the block's picks of held experts in an instant
span named
``/repro/train/expert_load#step=..,assigned=..,assigned_live=..,busiest=..``
(``src/repro/tracing.py::mark``; ``assigned_live`` counts the picks of
``(layer, expert)`` rows that still train).  The name carries the numbers
because the profiler turns a span's keyword arguments into event stats,
which the reduced trace does not keep.  Required per pick, each
``expert_flops_per_pick`` (the three matrices, ``bench/families/``): the
forward pass, its recomputation under ``remat`` "full", dX, and dW for the
live rows only, so the dW that Tier 0 still computes for frozen rows shows
as lost roofline.  The kernels are megablox's ``gmm`` (forward and dX) and
``tgmm`` (dW), found by their op names, which may carry transformation
prefixes.  A program that runs the kernels without the span reads nothing;
a window in which no held expert ran (a dense model) reads 0."""
import spans
import trace_reduce

SPAN = "/repro/train/expert_load"


def grouped_matmul_seconds(tr) -> float:
    """Device seconds of the ``gmm`` and ``tgmm`` kernels in the window,
    summed over the chips."""
    return 1e-9 * sum(e - s for ev in tr.ops.values() for s, e, n in ev
                      if "gmm" in trace_reduce.op_kind(n))


def span_args(event_name: str) -> dict:
    """``name#k=v,k=v`` -> ``{k: int(v)}``."""
    _, _, args = event_name.partition("#")
    pairs = (a.split("=", 1) for a in args.rstrip("#").split(",") if "=" in a)
    return {k: int(v) for k, v in pairs if v.isdigit()}


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    cell = ctx["cell"]
    loads = [a for a in (span_args(n) for _, _, n in tr.host
                         if spans.span_name(n) == SPAN)
             if "assigned" in a and "assigned_live" in a]
    busy = grouped_matmul_seconds(tr)
    if not loads and busy <= 0:
        return 0.0                       # no held expert ran in the window
    per_pick = getattr(cell.family, "expert_flops_per_pick", None)
    if per_pick is None or not loads or busy <= 0:
        return None
    passes = 3 if cell.traffic["trainer"]["remat"] == "full" else 2
    picks = sum(passes * a["assigned"] + a["assigned_live"] for a in loads)
    return 100.0 * picks * per_pick(cell) / peaks["bf16_flops"] / busy
