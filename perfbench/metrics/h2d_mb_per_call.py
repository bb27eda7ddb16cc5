"""h2d_mb_per_call: megabytes (10^6 bytes) per call that the program staged
from host arrays onto the device, its `/repro/sweep/h2d_bytes` counter, from
one traced call after the window (`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.h2d_mb_per_call()
