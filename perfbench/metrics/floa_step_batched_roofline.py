"""floa_step_batched_roofline: the fused OTA combine / parameter-server step
kernel's share of its roofline, 100 x (least time its bytes need at the
chip's HBM bandwidth) / (its summed device time in the trace), over every
launch in the window.  A launch reads an f32[S, U, D] gradient slab (its
largest 3-D operand in the HLO text, D the true width, unpadded) with the
[S, D] weight and noise rows.  Bound: bytes."""
from _roofline import read_kernel


def _lanes_u_d(shapes):
    lanes, u, d = max((s for _, s in shapes if len(s) == 3),
                      key=lambda s: s[0] * s[1] * s[2])
    return lanes, u, d


def read(ctx):
    return read_kernel(ctx, "floa_step_batched", _lanes_u_d)
