"""loss_eval_us_per_lane_round: device microseconds per lane-round in the
`sweep_loss` (post-update loss forward, aggregate norm) and `sweep_eval`
(eval_fn on its schedule) scopes, summed over chips, from one traced call
after the window (`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_lane_round_us("sweep_loss", "sweep_eval")
