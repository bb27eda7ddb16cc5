"""grads_us_per_lane_round: device microseconds per lane-round in the
`sweep_grads` scope (per-worker gradients, the [S, U, D] slab and its
gathers), summed over chips, from one traced call after the window
(`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_lane_round_us("sweep_grads")
