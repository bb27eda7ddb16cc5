"""stage_ms_per_call: host milliseconds per call in the program's
`sweep.stage` spans (host-to-device puts of the batches, parameters, keys,
and of each chunk's block), from one traced call after the window
(`_layers.py`)."""
from _layers import reading


def read(ctx):
    r = reading(ctx)
    return None if r is None else r.per_call_ms("sweep.stage")
