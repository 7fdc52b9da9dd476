"""The least work of a blocked 3-D transform, from its shapes alone.

Applying a b1 x b2 x b3 block transform to every block of an (N1, N2, N3)
plane takes ``b1 + b2 + b3`` multiply-adds per element, three staged
products within each block, whatever the program's lowering (dense,
block-sparse or in-block).  Its compulsory bytes: read the plane once,
write the result once, read the three b x b coefficient matrices once.
"""
from __future__ import annotations

import math


def blocked_transform_flops(dims, block) -> int:
    """2 * N1*N2*N3 * (b1 + b2 + b3)."""
    return 2 * math.prod(dims) * sum(block)


def blocked_transform_bytes(dims, block, itemsize: int = 4) -> int:
    """Plane in, plane out, each b x b coefficient matrix once."""
    return itemsize * (2 * math.prod(dims) + sum(b * b for b in block))


def least_time_s(dims, block, peaks: dict,
                 itemsize: int = 4) -> tuple[float, str]:
    """Least time of one blocked transform on one chip, and which peak
    bounds it (``peaks`` as ``work.load_peaks`` gives them)."""
    t_flops = blocked_transform_flops(dims, block) / peaks["bf16_flops_per_s"]
    t_bytes = (blocked_transform_bytes(dims, block, itemsize)
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")
