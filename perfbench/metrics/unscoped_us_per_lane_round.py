"""unscoped_us_per_lane_round: device microseconds per lane-round of the
sweep programs' ops under no layer scope (XLA's layout copies, the group
concatenation, pads, the per-round key split), summed over chips, from one
traced call after the window (`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_lane_round_us("unscoped")
