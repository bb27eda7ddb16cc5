"""Work of one `sort_columns` launch (kernels/defense_sort.py): an ascending
sort along the worker axis of a [U, D] block for each of S lanes (the vmap
over a defense group lifts into the kernel's grid), by an odd-even
transposition network of U passes.

  bytes  S x U x D x itemsize read, the same written: 2 S U D itemsize
  ops    S x D x U (U - 1) / 2 compare-exchanges, a min and a max each

Bound: bytes.  The ops are VPU min/max, not MXU FLOPs; at U = 10 they are
0.9 per byte moved, and against the chip's FLOP peak they would take a few
thousandths of the time the bytes take.  D is the width the kernel is
called on, padded to a multiple of its 2048-wide tile (its HLO shapes)."""

def cost(lanes: int, u: int, d: int, itemsize: int = 4) -> dict:
    return {"bytes": 2 * lanes * u * d * itemsize,
            "ops": lanes * d * u * (u - 1),
            "bound": "bytes"}
