"""sort_columns_roofline: the `sort_columns` kernel's share of its roofline,
100 x (least time its bytes need at the chip's HBM bandwidth) / (its
summed device time in the trace), over every launch in the window.  A
launch sorts an f32[S, U, D] block (S lanes of one defense family, D padded
to the kernel's tile), which its HLO output shape gives.  Bound: bytes."""
from _roofline import read_kernel


def _lanes_u_d(shapes):
    (_, (lanes, u, d)) = shapes[0]
    return lanes, u, d


def read(ctx):
    return read_kernel(ctx, "sort_columns", _lanes_u_d)
