"""screen_us_per_lane_round: device microseconds per lane-round in the
`sweep_screen` scope (the digital sign flip, the defense kernels or the
lax.switch selector, sort_columns among them, the screened lanes' PS
update), summed over chips, from one traced call after the window
(`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_lane_round_us("sweep_screen")
