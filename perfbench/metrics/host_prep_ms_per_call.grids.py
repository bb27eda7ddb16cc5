"""host_prep_ms_per_call.grids: host_prep_ms_per_call (see host_prep_ms_per_call.py), in the cells whose end-to-end
metric is grid_call_s."""
from host_prep_ms_per_call import read  # noqa: F401
