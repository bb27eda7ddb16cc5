"""trace_lower_ms_per_call.grids: trace_lower_ms_per_call (see trace_lower_ms_per_call.py), in the cells whose end-to-end
metric is grid_call_s."""
from trace_lower_ms_per_call import read  # noqa: F401
