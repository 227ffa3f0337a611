"""Share of the window the trainer spent waiting for its next input block
(the program's span ``/repro/train/prefetch_wait`` around the prefetcher's
``next``), whether or not the chip was busy meanwhile (``bench/spans.py``)."""
import spans


def read(ctx):
    if not spans.readable(ctx):
        return None
    return spans.share_inside(ctx["trace"],
                              spans.named("/repro/train/prefetch_wait"))
