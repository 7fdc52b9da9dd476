"""The least work of a transform, from its shapes alone.

Whatever kernels implement the dense three-mode transform
``Y = X x1 C1 x2 C2 x3 C3`` of an (N1, N2, N3) field, the chip has to do
its multiply-adds and move its compulsory bytes: read the field and the
three square coefficient matrices once, write the result once.  These
numbers do not change when the program's fusion, tiling or staging does,
so a roofline share built on them reads the same for any implementation.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def dense_transform_flops(dims) -> int:
    """2 * N1*N2*N3 * (N1 + N2 + N3): three staged mode products."""
    n1, n2, n3 = dims
    return 2 * n1 * n2 * n3 * (n1 + n2 + n3)


def dense_transform_bytes(dims, itemsize: int = 4) -> int:
    """Field in, field out, each coefficient matrix read once."""
    n1, n2, n3 = dims
    return itemsize * (2 * n1 * n2 * n3 + n1 * n1 + n2 * n2 + n3 * n3)


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def least_time_s(dims, peaks: dict, chips: int = 1,
                 itemsize: int = 4) -> tuple[float, str]:
    """Least time of one transform per chip, and which peak bounds it.

    On ``chips`` chips the work and the bytes are divided evenly: each chip
    holds and produces its share of the field.
    """
    t_flops = dense_transform_flops(dims) / chips / peaks["bf16_flops_per_s"]
    t_bytes = (dense_transform_bytes(dims, itemsize) / chips
               / peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")
