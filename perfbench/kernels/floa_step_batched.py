"""Work of one `floa_step_batched` launch (kernels/floa_aggregate.py): for
each of S lanes the fused OTA combine and parameter-server step over a
[U, D] gradient slab,

    gagg = coeffs @ G + bias + eps * z,   w_new = w - alpha * gagg.

  bytes  read G (S U D), w and z (2 S D); write w_new and gagg (2 S D):
         (U + 4) S D itemsize
  flops  per lane and column: U multiply-adds (2 U), the bias, the noise
         multiply-add and the step's multiply-add (5): (2 U + 5) S D

Bound: bytes (about 0.4 FLOP per byte at U = 4 or 10).  D is the true,
unpadded width the kernel is called on: its [S, U, D] slab and [S, D] rows
in the HLO shapes, with a ragged last block where D is no multiple of the
kernel's tile."""

def cost(lanes: int, u: int, d: int, itemsize: int = 4) -> dict:
    return {"bytes": (u + 4) * lanes * d * itemsize,
            "flops": (2 * u + 5) * lanes * d,
            "bound": "bytes"}
