"""combine_us_per_lane_round: device microseconds per lane-round in the
`sweep_combine` scope (standardization stats, channel, Markov and
participation draws, coefficients, noise, the OTA combine or the fused
floa_step_batched, jamming, cohort direction, the analog lanes' PS update),
summed over chips, from one traced call after the window (`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_lane_round_us("sweep_combine")
