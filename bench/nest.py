"""Bytes a loop nest moves at the least: every store cell it reads, read
once, and every cell it writes, written once, at 8 bytes (float64).

Computed from the program's shape (and, for an indirect access, from its
index array's values), so the number is the same whatever implements the
loop.  It is the numerator of a roofline share: the least time is these
bytes over the chip's HBM bandwidth.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

CELL_BYTES = 8


def _cells(ref, bounds, arrays: Dict[str, np.ndarray]):
    """(array name, index expression) of the cells ``ref`` touches."""

    if hasattr(ref, "index"):  # an indirect access array[idx[i + k] + off]
        (lo, hi), = bounds
        k = ref.index.offset_tuple()[0]
        sub = arrays[ref.index.array][lo + k:hi + k].astype(np.int64)
        return ref.array, (sub + ref.offset,)
    return ref.array, tuple(
        slice(lo + o, hi + o) for (lo, hi), o in zip(bounds, ref.offset_tuple())
    )


def nest_bytes(program, arrays: Dict[str, np.ndarray]) -> int:
    """``arrays``: the request's store as dense arrays with origin 0."""

    read = {a: np.zeros(v.shape, bool) for a, v in arrays.items()}
    written = {a: np.zeros(v.shape, bool) for a, v in arrays.items()}
    for s in program.statements:
        refs = list(s.reads) + ([s.guard] if s.guard is not None else [])
        for ref in refs:
            name, idx = _cells(ref, program.bounds, arrays)
            read[name][idx] = True
            if hasattr(ref, "index"):
                read[ref.index.array][_cells(ref.index, program.bounds,
                                             arrays)[1]] = True
        name, idx = _cells(s.write, program.bounds, arrays)
        written[name][idx] = True
        if hasattr(s.write, "index"):
            read[s.write.index.array][_cells(s.write.index, program.bounds,
                                             arrays)[1]] = True
    cells = sum(int(m.sum()) for m in read.values())
    cells += sum(int(m.sum()) for m in written.values())
    return CELL_BYTES * cells
