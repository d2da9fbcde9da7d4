"""The comparison that decides ``correct``: a served store against the plain
reference, normwise per array.

The arithmetic is the store diff the repository's chip smoke uses, kept
here so that later changes to the program cannot change the yardstick.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

import numpy as np


def dense(cells: Mapping[tuple, float]) -> Tuple[Tuple[int, ...], np.ndarray]:
    """A store array (``{index tuple: value}``) as (origin, dense array).
    Raises ``ValueError`` when the cells do not fill their bounding box."""

    keys = np.asarray(list(cells.keys()), dtype=np.int64)
    lo = keys.min(axis=0)
    shape = tuple((keys.max(axis=0) - lo + 1).tolist())
    if len(cells) != int(np.prod(shape)):
        raise ValueError("the store array does not fill its bounding box")
    out = np.zeros(shape, dtype=np.float64)
    out[tuple((keys - lo).T)] = np.fromiter(
        cells.values(), dtype=np.float64, count=len(cells)
    )
    return tuple(lo.tolist()), out


def dense_inputs(store: Mapping[str, Mapping]) -> Dict[str, np.ndarray]:
    """Every array of a store as a dense array; origins must be 0, as the
    configurations lay their stores out."""

    out = {}
    for name, cells in store.items():
        origin, arr = dense(cells)
        if any(origin):
            raise ValueError(f"array {name!r} does not start at 0: {origin}")
        out[name] = arr
    return out


def gap(got: Mapping[str, Mapping], want: Mapping[str, np.ndarray]) -> float:
    """The widest normwise gap over the reference's arrays:
    ``max |got - want| / max |want|``.  A served array that covers other
    cells than the reference, or holds a non-finite value, reads ``inf``."""

    worst = 0.0
    for name, ref in want.items():
        cells = got.get(name)
        if cells is None or len(cells) != ref.size:
            return float("inf")
        try:
            origin, arr = dense(cells)
        except ValueError:
            return float("inf")
        if any(origin) or arr.shape != ref.shape:
            return float("inf")
        if not np.isfinite(arr).all():
            return float("inf")
        scale = max(float(np.abs(ref).max()), np.finfo(np.float64).tiny)
        worst = max(worst, float(np.abs(arr - ref).max()) / scale)
    return worst
