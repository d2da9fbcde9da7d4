"""The ``hpcg_symgs`` configuration on the CPU at tiny grids: its plain
reference against HPCG's own code as written, and its cell through the
harness.

    python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src"), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402
import hpcg_literal  # noqa: E402
import nest  # noqa: E402

SEED = 2**35 + 29
CELL = "hpcg_symgs.g16-symgs"


def _config(nx, ny, nz):
    cfg = json.loads((BENCH / "configs" / "hpcg_symgs.json").read_text())
    cfg.update(nx=nx, ny=ny, nz=nz)
    mod = gen.load_module(BENCH / "configs" / "hpcg_symgs.py", "cfg_symgs")
    shared = mod.setup(cfg, {}, SEED)
    shared["seed"] = SEED
    return cfg, mod, shared


@pytest.mark.parametrize("grid", [(4, 4, 4), (3, 4, 5)])
def test_reference_is_hpcg_as_written(grid):
    cfg, mod, shared = _config(*grid)
    store = mod.request_store(cfg, {}, shared, {}, 0, 7, None)
    inputs = check.dense_inputs(store)
    want = hpcg_literal.compute_symgs_ref(
        hpcg_literal.generate_problem(*grid), inputs["r"].tolist(),
        inputs["x"].tolist())
    got = mod.reference(cfg, shared, {}, inputs, np.float64)
    assert got["x"].tolist() == want
    assert len(shared["row"]) == 2 * mod.nonzeros(cfg)
    low = mod.reference(cfg, shared, {}, inputs, np.float32)["x"]
    assert 0 < np.abs(low - got["x"]).max() < 1e-5


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench") / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    path = root / "bench" / "configs" / "hpcg_symgs.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    nx=4, ny=4, nz=4)))
    return root


def _run(root, **kw):
    return harness.run_cell(harness.Bench(root), CELL, SEED, 1.5, False,
                            t_process=0.0, require_chip=False, **kw)


def test_the_cell_runs_correct_at_a_tiny_grid(tiny_root):
    logged = []
    r = _run(tiny_root, log=lambda **kw: logged.append(kw))
    assert r["correct"], r["checks"]
    case = logged[0]["run"]["cases"][0]
    # S0 and S2 run once a row a sweep: 2 * 64 lanes each
    assert case["instances"] == 2000 + 2 * 128
    assert logged[0]["run"]["xla_traces_in_window"] == 0


def test_the_control_in_the_programs_place_reads_not_correct(tiny_root):
    r = _run(tiny_root, control=np.float32)
    assert r["correct"] is False
    assert r["checks"]["gap"]["value"] > r["checks"]["gap"]["limit"]
    assert r["checks"]["program_gap"]["value"] <= r["checks"]["gap"]["limit"]


def test_nest_bytes_count_slots_guards_and_rows(tiny_root):
    cell = harness.Cell(harness.Bench(tiny_root), CELL, 1)
    prog, _ = cell.program({})
    arrays = check.dense_inputs(cell.store({}, 0, 0))
    slots, rows = 2 * 1000, 64
    # read: row, col, v, first, last over every slot; r, diag, s, x over
    # every row; written: s, x
    assert nest.nest_bytes(prog, arrays) == 8 * (5 * slots + 4 * rows
                                                 + 2 * rows)
