"""Model FLOP utilization of the compiled step: the FLOPs a step requires
(``bench/flops.py``: forward, dX, dW of live matrices and the head, causal
attention; no recomputation) times the steps drained in the window, over the
seconds in which the device ran an operation in the window (the profiler
trace's busy time, so the host's stalls do not count) and the chips' bf16
peak (``bench/peaks.json``)."""
import flops


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or ctx["steps"] <= 0:
        return None
    busy = tr.busy_s()
    if busy <= 0:
        return None
    cell = ctx["cell"]
    done = flops.step_flops(cell) * ctx["steps"]
    return 100.0 * done / busy / (cell.chips * peaks["bf16_flops"])
