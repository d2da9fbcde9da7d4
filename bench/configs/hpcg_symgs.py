"""HPCG's symmetric Gauss-Seidel smoother as a served loop nest, with its
plain reference.

One request is one call of HPCG 3.1's ``ComputeSYMGS_ref`` on the 27-point
operator of ``GenerateProblem_ref`` over an nx x ny x nz local grid.  The
two sweeps are one 1-D loop over 2 * nnz CSR slots: the forward order (rows
ascending, each row's columns ascending), then the backward order (rows
descending, columns ascending).  Each slot runs HPCG's row loop, operation
for operation::

    s[row[k]] = r[row[k]]                                  if first[k] > 0
    s[row[k]] = s[row[k]] - v[k] * x[col[k]]
    x[row[k]] = (s[row[k]] + x[row[k]] * diag[row[k]]) / diag[row[k]]
                                                           if last[k] > 0

planned with ``deps="inspect"``: the guards read ``first`` and ``last``,
which no statement writes, so the inspector resolves them and lays out only
the slots that run those statements.  Each request smooths a fresh residual
``r`` from ``x = 0``, as ``ComputeMG_ref`` does before the finest level's
pre-smoother.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import gen


def _seed(r):
    return r


def _subtract(s, v, x):
    return s - v * x


def _divide(s, x, d):
    return (s + x * d) / d


def program(cfg: dict, sizes: Dict[str, int]):
    from repro.core import PlanOptions
    from repro.core.ir import ArrayRef, IndirectRef, LoopProgram, Statement

    # the loop needs the inspector to resolve guards over read-only arrays:
    # without that, every slot's S0 and S2 count as writers of their row,
    # and S0's level table alone is dense over every slot
    from repro.core.inspector import resolved_guard_arrays  # noqa: F401

    row = ArrayRef("row", 0)
    s, x = IndirectRef("s", row), IndirectRef("x", row)
    diag = IndirectRef("diag", row)
    prog = LoopProgram(
        statements=(
            Statement("S0", s, (IndirectRef("r", row),), compute=_seed,
                      guard=ArrayRef("first", 0)),
            Statement("S1", s,
                      (s, ArrayRef("v", 0), IndirectRef("x", ArrayRef("col", 0))),
                      compute=_subtract),
            Statement("S2", x, (s, x, diag), compute=_divide,
                      guard=ArrayRef("last", 0)),
        ),
        bounds=((0, 2 * nonzeros(cfg)),),
    )
    return prog, PlanOptions(deps="inspect")


def nonzeros(cfg: dict) -> int:
    """The 27-point operator's nonzeros: each dimension of n points has
    3n - 2 neighbour pairs, the point itself included."""

    return (3 * cfg["nx"] - 2) * (3 * cfg["ny"] - 2) * (3 * cfg["nz"] - 2)


def generate_problem(cfg: dict) -> List[Tuple[List[int], List[float], float]]:
    """``GenerateProblem_ref`` for one rank: per row, its columns in the
    order the ``sz, sy, sx`` loops visit them (ascending), its values, and
    its diagonal value."""

    nx, ny, nz = cfg["nx"], cfg["ny"], cfg["nz"]
    rows = []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                current = iz * nx * ny + iy * nx + ix
                cols, vals, diag = [], [], None
                for sz in (-1, 0, 1):
                    if not 0 <= iz + sz < nz:
                        continue
                    for sy in (-1, 0, 1):
                        if not 0 <= iy + sy < ny:
                            continue
                        for sx in (-1, 0, 1):
                            if not 0 <= ix + sx < nx:
                                continue
                            col = current + sz * nx * ny + sy * nx + sx
                            if col == current:
                                diag = cfg["diagonal"]
                                vals.append(diag)
                            else:
                                vals.append(cfg["off_diagonal"])
                            cols.append(col)
                rows.append((cols, vals, diag))
    return rows


def setup(cfg: dict, traffic: dict, seed: int) -> dict:
    """The operator, as CSR rows for the reference and as the slot arrays
    of the loop, shared by every request."""

    csr = generate_problem(cfg)
    nrow = len(csr)
    order = list(range(nrow)) + list(range(nrow - 1, -1, -1))
    row, col, v, first, last = [], [], [], [], []
    for i in order:
        cols, vals, _diag = csr[i]
        n = len(cols)
        row += [float(i)] * n
        col += [float(c) for c in cols]
        v += vals
        first += [1.0] + [0.0] * (n - 1)
        last += [0.0] * (n - 1) + [1.0]
    keys_s = [(k,) for k in range(len(row))]
    keys_n = [(i,) for i in range(nrow)]
    slots = {name: dict(zip(keys_s, vals)) for name, vals in
             (("row", row), ("col", col), ("v", v), ("first", first),
              ("last", last))}
    return {
        "csr": csr,
        **slots,
        "diag": dict(zip(keys_n, (d for _c, _v, d in csr))),
        "zero": dict.fromkeys(keys_n, 0.0),
        "keys_n": keys_n,
    }


def request_store(cfg, traffic, shared, sizes, client, index, prev):
    r = gen.rng(shared["seed"], 3, index).uniform(-1.0, 1.0,
                                                  len(shared["keys_n"]))
    store = {a: shared[a] for a in ("row", "col", "v", "first", "last",
                                    "diag")}
    store.update(r=dict(zip(shared["keys_n"], r.tolist())),
                 x=shared["zero"], s=shared["zero"])
    return store


def reference(cfg, shared, sizes, inputs: Dict[str, np.ndarray], dtype):
    """``ComputeSYMGS_ref``'s two loops over the CSR rows as written, every
    operation rounded to ``dtype``; reads ``r`` and ``x`` of the request,
    never the slot arrays."""

    cast = (lambda a: a.tolist()) if dtype == np.float64 else list
    rv = cast(inputs["r"].astype(dtype))
    xv = cast(inputs["x"].astype(dtype))
    csr = [(cols, cast(np.asarray(vals, dtype)), dtype(diag))
           for cols, vals, diag in shared["csr"]]
    nrow = len(csr)
    for i in list(range(nrow)) + list(range(nrow - 1, -1, -1)):
        cols, vals, diag = csr[i]
        total = rv[i]
        for j in range(len(cols)):
            total -= vals[j] * xv[cols[j]]
        total += xv[i] * diag
        xv[i] = total / diag
    return {"x": np.asarray(xv, dtype=np.float64)}
