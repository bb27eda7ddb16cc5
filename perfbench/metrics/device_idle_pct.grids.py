"""device_idle_pct.grids: device_idle_pct (see device_idle_pct.py), in the cells whose end-to-end
metric is grid_call_s."""
from device_idle_pct import read  # noqa: F401
