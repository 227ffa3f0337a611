"""Share of the window in which the chip was idle while no span of the
program (``/repro/...``, on any host thread) was open: idle time the
program's spans do not account for, averaged over the cell's chips
(``bench/spans.py``)."""
import spans


def read(ctx):
    if not spans.readable(ctx):
        return None
    return spans.idle_share_outside(ctx["trace"], spans.ours)
