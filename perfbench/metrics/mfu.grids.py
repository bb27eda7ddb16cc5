"""mfu.grids: mfu (see mfu.py), in the cells whose end-to-end
metric is grid_call_s."""
from mfu import read  # noqa: F401
