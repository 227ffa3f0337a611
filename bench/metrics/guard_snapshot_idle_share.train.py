"""Share of the window in which the chip was idle while the trainer took the
numerics guard's boundary snapshot (the program's span
``/repro/train/guard_snapshot``: the state expanded to the checkpoint layout
and pulled whole to host RAM), averaged over the cell's chips
(``bench/spans.py``)."""
import spans


def read(ctx):
    if not spans.readable(ctx):
        return None
    return spans.idle_share_under(ctx["trace"],
                                  spans.named("/repro/train/guard_snapshot"))
