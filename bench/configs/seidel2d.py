"""PolyBench/C 4.2 ``seidel-2d`` as a served loop nest, with its plain
reference.

One request is one sweep of the kernel's body over ``1 <= i, j <= N-2``::

    A[i][j] = (A[i-1][j-1] + A[i-1][j] + A[i-1][j+1]
               + A[i][j-1] + A[i][j] + A[i][j+1]
               + A[i+1][j-1] + A[i+1][j] + A[i+1][j+1]) / 9.0

in float64.  PolyBench's ``t`` loop is carried by the traffic (a chained
mix feeds each reply back as the next request's grid).  Dependences are
(0,1), (1,-1), (1,0) and (1,1): a mixed-sign recurrence that the strategy
auction has to skew or chunk.  Grids are uniform in [-1, 1) from the seed
(PolyBench's own initialisation is a fixed formula).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import gen

OFFSETS = (
    (-1, -1), (-1, 0), (-1, 1),
    (0, -1), (0, 0), (0, 1),
    (1, -1), (1, 0), (1, 1),
)


def _body(a0, a1, a2, a3, a4, a5, a6, a7, a8):
    return (a0 + a1 + a2 + a3 + a4 + a5 + a6 + a7 + a8) / 9.0


def program(cfg: dict, sizes: Dict[str, int]):
    """The nest at ``sizes["N"]`` and the options it is planned under."""

    from repro.core import PlanOptions
    from repro.core.ir import ArrayRef, LoopProgram, Statement

    n = sizes["N"]
    prog = LoopProgram(
        statements=(
            Statement(
                "S1",
                ArrayRef("A", (0, 0)),
                tuple(ArrayRef("A", o) for o in OFFSETS),
                compute=_body,
            ),
        ),
        bounds=((1, n - 1), (1, n - 1)),
    )
    return prog, PlanOptions()


def setup(cfg: dict, traffic: dict, seed: int) -> dict:
    return {"keys": {}}


def _keys(shared: dict, n: int) -> list:
    keys = shared["keys"].get(n)
    if keys is None:
        keys = shared["keys"][n] = [(i, j) for i in range(n) for j in range(n)]
    return keys


def request_store(cfg, traffic, shared, sizes, client, index, prev):
    """The grid of one request: the client's previous reply in a chained
    mix, else a grid drawn for this client (chained) or this request."""

    n = sizes["N"]
    if prev is not None:
        return {"A": prev["A"]}
    purpose = (1, client) if traffic.get("chain") else (2, index)
    vals = gen.rng(shared["seed"], *purpose).uniform(-1.0, 1.0, n * n)
    return {"A": dict(zip(_keys(shared, n), vals.tolist()))}


def reference(cfg, shared, sizes, inputs: Dict[str, np.ndarray], dtype):
    """One sweep in the order PolyBench writes it, every operation rounded
    to ``dtype`` (Python floats for float64, NumPy scalars otherwise)."""

    a = inputs["A"].astype(dtype)
    rows = a.tolist() if dtype == np.float64 else [list(r) for r in a]
    nine = dtype(9.0)
    n = len(rows)
    for i in range(1, n - 1):
        up, row, dn = rows[i - 1], rows[i], rows[i + 1]
        for j in range(1, n - 1):
            row[j] = (
                up[j - 1] + up[j] + up[j + 1]
                + row[j - 1] + row[j] + row[j + 1]
                + dn[j - 1] + dn[j] + dn[j + 1]
            ) / nine
    return {"A": np.asarray(rows, dtype=np.float64)}
