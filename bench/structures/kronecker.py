"""Graph500's Kronecker generator (the reference ``kronecker_generator``
of the specification, vectorized): ``edgefactor * 2**SCALE`` edges over
``2**SCALE`` vertices, one bit of each endpoint per level drawn from the
configuration's initiator (A, B, C; D = 1 - A - B - C).  The vertex
labels and the edge order are permuted by the caller (``gen.edges``)."""

from __future__ import annotations

from typing import Tuple

import numpy as np


def edges(cfg: dict, params: dict,
          r: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    scale = cfg["SCALE"]
    m = cfg["edgefactor"] << scale
    init = cfg["initiator"]
    ab = init["A"] + init["B"]
    c_norm = init["C"] / (1.0 - ab)
    a_norm = init["A"] / ab
    row = np.zeros(m, np.int64)
    col = np.zeros(m, np.int64)
    for bit in range(scale):
        row_bit = r.random(m) > ab
        col_bit = r.random(m) > np.where(row_bit, c_norm, a_norm)
        row += row_bit.astype(np.int64) << bit
        col += col_bit.astype(np.int64) << bit
    return row, col
