"""Device: share (%) of the traced window in which no operation ran on a
chip, mean over the cell's chips (profiler trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)
