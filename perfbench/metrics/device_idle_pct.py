"""device_idle_pct: the share of the traced window in which no operation
ran on the chip, 100 x (1 - busy / window), busy being the union of the
chip's operation intervals (trace_reduce); the mean over the cell's chips."""


def read(ctx):
    s = ctx["summary"]
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
