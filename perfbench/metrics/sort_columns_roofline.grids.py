"""sort_columns_roofline.grids: sort_columns_roofline (see sort_columns_roofline.py), in the cells whose end-to-end
metric is grid_call_s."""
from sort_columns_roofline import read  # noqa: F401
