"""Share of the window in which the chip was idle while the trainer took the
numerics guard's boundary snapshot (the program's span
``/repro/train/guard_snapshot``: the state as the compiled step holds it,
packed moments and all, copied to host RAM in groups of at most 2 GiB, with
no expansion), averaged over the cell's chips (``bench/spans.py``)."""
import spans


def read(ctx):
    if not spans.readable(ctx):
        return None
    return spans.idle_share_under(ctx["trace"],
                                  spans.named("/repro/train/guard_snapshot"))
