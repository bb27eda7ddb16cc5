"""h2d_mb_per_call.grids: h2d_mb_per_call (see h2d_mb_per_call.py), in the cells whose end-to-end
metric is grid_call_s."""
from h2d_mb_per_call import read  # noqa: F401
