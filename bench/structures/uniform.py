"""``edgefactor * 2**SCALE`` edges with both endpoints uniform over
``2**SCALE`` vertices: Erdos-Renyi G(n, m), drawn with replacement."""

from __future__ import annotations

from typing import Tuple

import numpy as np


def edges(cfg: dict, params: dict,
          r: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    n, m = 1 << cfg["SCALE"], cfg["edgefactor"] << cfg["SCALE"]
    return r.integers(0, n, m), r.integers(0, n, m)
