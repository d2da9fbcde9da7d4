"""HPCG 3.1's ``GenerateProblem_ref`` (one rank) and ``ComputeSYMGS_ref``,
transcribed as written in C: CSR arrays ``nonzerosInRow``, ``mtxIndL``,
``matrixValues`` and ``matrixDiagonal``, then the forward and the backward
sweep.  The yardstick the ``hpcg_symgs`` configuration's program and its
``reference()`` are both held to; it shares no code with either."""

from __future__ import annotations

from typing import List


def generate_problem(nx: int, ny: int, nz: int):
    nrow = nx * ny * nz
    nonzeros_in_row = [0] * nrow
    mtx_ind_l: List[List[int]] = [[] for _ in range(nrow)]
    matrix_values: List[List[float]] = [[] for _ in range(nrow)]
    matrix_diagonal = [0.0] * nrow
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                current_local_row = iz * nx * ny + iy * nx + ix
                for sz in range(-1, 2):
                    if iz + sz > -1 and iz + sz < nz:
                        for sy in range(-1, 2):
                            if iy + sy > -1 and iy + sy < ny:
                                for sx in range(-1, 2):
                                    if ix + sx > -1 and ix + sx < nx:
                                        curcol = (current_local_row
                                                  + sz * nx * ny + sy * nx + sx)
                                        if curcol == current_local_row:
                                            matrix_diagonal[current_local_row] = 26.0
                                            matrix_values[current_local_row].append(26.0)
                                        else:
                                            matrix_values[current_local_row].append(-1.0)
                                        mtx_ind_l[current_local_row].append(curcol)
                                        nonzeros_in_row[current_local_row] += 1
    return nonzeros_in_row, mtx_ind_l, matrix_values, matrix_diagonal


def compute_symgs_ref(problem, rv: List[float], xv: List[float]) -> List[float]:
    """``ComputeSYMGS_ref`` on float64 lists; returns the new ``x``."""

    nonzeros_in_row, mtx_ind_l, matrix_values, matrix_diagonal = problem
    xv = list(xv)
    nrow = len(nonzeros_in_row)
    for i in range(nrow):
        current_values = matrix_values[i]
        current_col_indices = mtx_ind_l[i]
        current_diagonal = matrix_diagonal[i]
        total = rv[i]
        for j in range(nonzeros_in_row[i]):
            cur_col = current_col_indices[j]
            total -= current_values[j] * xv[cur_col]
        total += xv[i] * current_diagonal
        xv[i] = total / current_diagonal
    for i in range(nrow - 1, -1, -1):
        current_values = matrix_values[i]
        current_col_indices = mtx_ind_l[i]
        current_diagonal = matrix_diagonal[i]
        total = rv[i]
        for j in range(nonzeros_in_row[i]):
            cur_col = current_col_indices[j]
            total -= current_values[j] * xv[cur_col]
        total += xv[i] * current_diagonal
        xv[i] = total / current_diagonal
    return xv
