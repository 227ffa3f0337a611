"""Share of its roofline that the freeze machinery's kernels
(``grades_norm``, ``masked_adamw``; ``core/grades.py``,
``optim/optimizer.py``) reach in the window: the least HBM bytes a step
requires of them over live rows only (``bench/flops.py``), times the steps in
the window, over the HBM peak, over the kernels' summed device time.  Skipping
frozen tiles can raise it; it cannot pass 100% on sound code."""
import flops

KERNELS = ("grades_norm", "masked_adamw")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or ctx["steps"] <= 0:
        return None
    need = flops.grades_bytes_per_step(ctx["cell"])
    least = sum(need[k] for k in KERNELS) * ctx["steps"] \
        / peaks["hbm_bytes_per_s"]
    busy = sum(tr.kernel(k)[1] for k in KERNELS)
    if busy <= 0:
        return None
    return 100.0 * least / busy
