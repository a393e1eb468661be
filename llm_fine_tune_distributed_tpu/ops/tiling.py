"""What every Pallas kernel of the package asks of the chip's tiles: a VMEM
buffer's size as Mosaic lays it out, the cap one kernel may ask for, a width in
whole lane registers, a block's rows added up by sublane. One place, so that the
kernels' modules (``flash_attention``, ``moe``, ``gated_delta``, ``rope``) read
it from here and not from one another."""

from __future__ import annotations

import numpy as np

# What one kernel may ask for of a v5e core's 128 MiB of VMEM: the rest stays
# with XLA's own fusions around the kernel.
VMEM_CAP_BYTES = 100 * 1024 * 1024


def tiled_bytes(shape, dtype) -> int:
    """Bytes of one VMEM buffer of ``shape``: the last dim pads to 128 lanes
    and the one before to the dtype's sublane tile (8 rows of 32 bits)."""
    itemsize = np.dtype(dtype).itemsize
    *lead, rows, lanes_ = shape
    sublanes = 8 * (4 // itemsize)
    rows = -(-rows // sublanes) * sublanes
    return int(np.prod(lead, dtype=np.int64)) * rows * lanes(lanes_) * itemsize


def lanes(d: int) -> int:
    """``d`` rounded up to whole 128-lane registers."""
    return -(-d // 128) * 128


def by_eights(x):
    """``[rows, width]`` -> ``[8, width]``: rows 8 apart added (whole tiles; the caller adds the 8 sublanes up)."""
    return x.reshape(x.shape[0] // 8, 8, x.shape[1]).sum(axis=0)
