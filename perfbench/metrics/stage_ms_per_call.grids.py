"""stage_ms_per_call.grids: stage_ms_per_call (see stage_ms_per_call.py), in the cells whose end-to-end
metric is grid_call_s."""
from stage_ms_per_call import read  # noqa: F401
