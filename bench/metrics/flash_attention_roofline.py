"""Share of its roofline that the flash attention kernels (``flash_fwd``,
``flash_dq``, ``flash_dkv``) reach in the window: for each call, the least
time the chip could take, the larger of its required FLOPs over the bf16 peak
and its least HBM bytes over the HBM peak (``bench/flops.py``), summed over
the calls in the trace, over the kernels' summed device time.  Every call
counts its own required work, so the forward recomputed under remat counts as
a forward; the backward kernels count the two matmuls each requires, not the
score tiles they recompute."""
import flops

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    cell = ctx["cell"]
    work = flops.attention_flops_per_call(cell)
    nbytes = flops.flash_bytes_per_call(cell)
    least = busy = 0.0
    for k in KERNELS:
        calls, seconds = tr.kernel(k)
        least += calls * max(work / peaks["bf16_flops"],
                             nbytes[k] / peaks["hbm_bytes_per_s"])
        busy += seconds
    if busy <= 0:
        return None
    return 100.0 * least / busy
