"""The smoke programs: one of each schedule shape the served path lowers,
at the sizes ``chip_smoke.py`` runs them on one chip.

Each entry is a :class:`~repro.core.ir.LoopProgram` built with the IR plus
the :class:`~repro.core.parallelizer.PlanOptions` it is planned under:

* ``skew_recurrence`` — ``a[i,j] = f(a[i-1,j+1])``, Δ=(1,-1), 1024×1024
  (1,048,576 instances): a mixed-sign recurrence the auction chunks;
* ``double_skew`` — a relaxation sweep, Δ ∈ {(1,-2), (1,1)}, 512×512: the
  auction skews it;
* ``alg6`` — paper Alg. 6 at 65,536 iterations: acyclic, ~131k levels of
  width ≤ 2;
* ``sparse_matvec`` — COO ``y[row[k]] += …`` over 65,536 nonzeros of a
  4096×4096 matrix, index arrays drawn from the seed, ``deps="inspect"``.

Stores come from :func:`seeded_store` (NumPy's generator), never from
:meth:`LoopProgram.initial_store`, whose ``hash()`` of strings changes from
process to process.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from repro.core.inspector import sparse_matvec
from repro.core.ir import ArrayRef, LoopProgram, Statement, paper_alg6
from repro.core.parallelizer import PlanOptions

__all__ = ["SMOKE_PROGRAMS", "seeded_store", "smoke_program"]

SMOKE_PROGRAMS = ("skew_recurrence", "double_skew", "alg6", "sparse_matvec")

# store cells beyond the loop box on each side: covers the widest offset
# (double_skew reads a[i-1, j+2])
PAD = 2
# sparse_matvec's matrix order: 65,536 nonzeros are 16 per row on average
SPMV_ORDER = 4096


def _relax(ne: float, nw: float) -> float:
    """Bounded, unlike the IR's default combiner, which grows ~(4/3)^i
    down this recurrence: past float32's range, where float64 emulated on a
    TPU overflows."""

    return 0.5 * (ne + nw) + 1.0


def smoke_program(name: str) -> Tuple[LoopProgram, PlanOptions]:
    """The named smoke program at its smoke size, with its plan options."""

    if name == "skew_recurrence":
        return LoopProgram(
            statements=(
                Statement(
                    "S1", ArrayRef("a", (0, 0)), (ArrayRef("a", (-1, 1)),)
                ),
            ),
            bounds=((0, 1024), (0, 1024)),
        ), PlanOptions()
    if name == "double_skew":
        return LoopProgram(
            statements=(
                Statement(
                    "S1",
                    ArrayRef("a", (0, 0)),
                    (ArrayRef("a", (-1, 2)), ArrayRef("a", (-1, -1))),
                    compute=_relax,
                ),
            ),
            bounds=((0, 512), (0, 512)),
        ), PlanOptions()
    if name == "alg6":
        return paper_alg6(65_537), PlanOptions()
    if name == "sparse_matvec":
        prog = sparse_matvec(16 * SPMV_ORDER)
        return prog, PlanOptions(deps="inspect")
    raise KeyError(f"unknown smoke program {name!r}; expected {SMOKE_PROGRAMS}")


def seeded_store(prog: LoopProgram, seed: int) -> Dict[str, dict]:
    """A full store over the loop box widened by :data:`PAD`: data arrays
    uniform in [-5, 5), index arrays integers in [0, SPMV_ORDER)."""

    rng = np.random.default_rng(seed)
    axes = [np.arange(lo - PAD, hi + PAD) for lo, hi in prog.bounds]
    points = np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(
        -1, len(axes)
    )
    keys = list(map(tuple, points.tolist()))
    index_arrays = set(prog.index_arrays())
    store = {}
    for arr in prog.arrays():  # a fixed order, so the draws are too
        if arr in index_arrays:
            vals = rng.integers(0, SPMV_ORDER, len(keys)).astype(np.float64)
        else:
            vals = rng.uniform(-5.0, 5.0, len(keys))
        store[arr] = dict(zip(keys, vals.tolist()))
    return store
