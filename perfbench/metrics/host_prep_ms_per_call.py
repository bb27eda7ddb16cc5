"""host_prep_ms_per_call: host milliseconds per call in the program's
`sweep.spec`, `sweep.init` and `sweep.prepare` spans (grid build, engine
build, parameter stacking, keys, flatten, lane permute and pad, the
programs' build), from one traced call after the window (`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_call_ms("sweep.spec", "sweep.init", "sweep.prepare")
