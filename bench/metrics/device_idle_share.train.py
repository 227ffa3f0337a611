"""Share of the window in which no operation ran on the device: 1 minus the
union of the device-op intervals of the profiler trace over the window,
averaged over the cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
