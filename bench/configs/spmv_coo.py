"""COO sparse matrix-vector product as a served loop nest, with its plain
reference.

    for k in range(nnz):  y[row[k]] = y[row[k]] + v[k] * x[col[k]]

written with ``IndirectRef`` and planned with ``deps="inspect"``: the
inspector serializes exactly the nonzeros that share a row, so the heaviest
row sets the number of levels.  The matrix has Graph500's size for
``SCALE`` and ``edgefactor`` (2**SCALE rows, edgefactor * 2**SCALE
nonzeros); its structure comes from the traffic file (the same degrees in
every run), its vertex labels and the order of its nonzeros from the seed.
Each request multiplies it by a fresh ``x`` with fresh values ``v``, both
uniform in [-1, 1), into ``y = 0``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import gen


def _body(y, v, x):
    return y + v * x


def program(cfg: dict, sizes: Dict[str, int]):
    from repro.core import PlanOptions
    from repro.core.ir import ArrayRef, IndirectRef, LoopProgram, Statement

    nnz = cfg["edgefactor"] << cfg["SCALE"]
    y = IndirectRef("y", ArrayRef("row", 0))
    prog = LoopProgram(
        statements=(
            Statement(
                "S1",
                y,
                (y, ArrayRef("v", 0), IndirectRef("x", ArrayRef("col", 0))),
                compute=_body,
            ),
        ),
        bounds=((0, nnz),),
    )
    return prog, PlanOptions(deps="inspect")


def setup(cfg: dict, traffic: dict, seed: int) -> dict:
    """The run's matrix structure, shared by every request."""

    n, nnz = 1 << cfg["SCALE"], cfg["edgefactor"] << cfg["SCALE"]
    row, col = gen.edges(traffic["structure"], cfg, seed)
    keys_n = [(k,) for k in range(n)]
    keys_m = [(k,) for k in range(nnz)]
    return {
        "row": dict(zip(keys_m, row.astype(np.float64).tolist())),
        "col": dict(zip(keys_m, col.astype(np.float64).tolist())),
        "y": dict.fromkeys(keys_n, 0.0),
        "keys_n": keys_n,
        "keys_m": keys_m,
        "heaviest_row": int(np.bincount(row, minlength=n).max()),
    }


def request_store(cfg, traffic, shared, sizes, client, index, prev):
    r = gen.rng(shared["seed"], 3, index)
    v = r.uniform(-1.0, 1.0, len(shared["keys_m"]))
    x = r.uniform(-1.0, 1.0, len(shared["keys_n"]))
    return {
        "row": shared["row"],
        "col": shared["col"],
        "y": shared["y"],
        "v": dict(zip(shared["keys_m"], v.tolist())),
        "x": dict(zip(shared["keys_n"], x.tolist())),
    }


def reference(cfg, shared, sizes, inputs: Dict[str, np.ndarray], dtype):
    """The loop as written, one nonzero after another, every operation
    rounded to ``dtype``."""

    row = inputs["row"].astype(np.int64).tolist()
    col = inputs["col"].astype(np.int64).tolist()
    cast = (lambda a: a.tolist()) if dtype == np.float64 else list
    v = cast(inputs["v"].astype(dtype))
    x = cast(inputs["x"].astype(dtype))
    y = cast(inputs["y"].astype(dtype))
    for k in range(len(row)):
        r = row[k]
        y[r] = y[r] + v[k] * x[col[k]]
    return {"y": np.asarray(y, dtype=np.float64)}
