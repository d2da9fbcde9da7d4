"""HPCG's symmetric Gauss-Seidel (the benchmark's ``hpcg_symgs``
configuration) served through ``PlanService`` at a 4^3 grid, whose rows are
corners, edges, faces and one interior block: bit-equal to HPCG's own code
as written, with the levels and counters the inspector's live instances
give."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
for p in (str(BENCH), str(BENCH / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import gen  # noqa: E402
import hpcg_literal  # noqa: E402

GRID = 4
SEED = 2**33 + 5


def symgs(grid: int = GRID):
    """(module, configuration, program, options, request store) of the
    configuration at an ``grid``^3 local grid."""

    cfg = json.loads((BENCH / "configs" / "hpcg_symgs.json").read_text())
    cfg.update(nx=grid, ny=grid, nz=grid)
    mod = gen.load_module(BENCH / "configs" / "hpcg_symgs.py", "cfg_symgs")
    shared = mod.setup(cfg, {}, SEED)
    shared["seed"] = SEED
    prog, options = mod.program(cfg, {})
    store = mod.request_store(cfg, {}, shared, {}, 0, 0, None)
    return mod, cfg, prog, options, store


def longest_path_levels(prog, store) -> int:
    """Levels of the live instances, each one level after every earlier
    live instance it conflicts with (a write against any access of its
    cell, a read against a write) and after the live statements before it
    in its own slot.  S0 and S2 run where ``first`` and ``last`` are set."""

    from repro.core.ir import ref_cell

    last_write, last_access = {}, {}
    depth = 0
    for it in prog.iterations():
        after = 0
        for s in prog.statements:
            if s.guard is not None and not store[s.guard.array][it] > 0:
                continue
            reads = [(r.array, ref_cell(r, it, store)) for r in s.reads]
            write = (s.write.array, ref_cell(s.write, it, store))
            lvl = max([after]
                      + [last_write.get(c, -1) + 1 for c in reads]
                      + [last_access.get(write, -1) + 1])
            for c in reads:
                last_access[c] = max(last_access.get(c, -1), lvl)
            last_write[write] = lvl
            last_access[write] = max(last_access.get(write, -1), lvl)
            after = lvl + 1
            depth = max(depth, lvl + 1)
    return depth


@pytest.fixture(scope="module")
def served():
    """One SymGS through a fresh ``PlanService``, with the counters it
    moved."""

    from repro.compile import clear_compile_cache
    from repro.core import clear_inspector_cache
    from repro.core.wavefront import _DenseStore
    from repro.obs import metrics
    from repro.serve import PlanService, ServiceOptions

    mod, cfg, prog, options, store = symgs()
    clear_compile_cache()
    clear_inspector_cache()
    names = ("xla.levels", "xla.lanes_live", "xla.lanes_padded",
             "inspector.guarded_out")
    before = {n: metrics.counter(n).value for n in names}
    svc = PlanService(ServiceOptions(backend="xla", workers=1))
    try:
        res = svc.submit(prog, options, store=store, run=True).result()
    finally:
        svc.close()
    moved = {n: metrics.counter(n).value - before[n] for n in names}
    case, hit = res.executable.compiled.prepare(prog, _DenseStore(store))
    assert hit
    return dict(mod=mod, cfg=cfg, prog=prog, store=store, out=res.store,
                case=case, moved=moved)


def test_one_symgs_is_hpcg_as_written(served):
    store = served["store"]
    nrow = GRID**3
    r = [store["r"][(i,)] for i in range(nrow)]
    want = hpcg_literal.compute_symgs_ref(
        hpcg_literal.generate_problem(GRID, GRID, GRID), r, [0.0] * nrow)
    assert [served["out"]["x"][(i,)] for i in range(nrow)] == want


def test_written_arrays_are_s_and_x(served):
    assert served["case"].written == ("s", "x")
    assert {s.write.array for s in served["prog"].statements} == {"s", "x"}


def test_levels_are_the_longest_path_over_live_instances(served):
    case = served["case"]
    assert case.n_levels == longest_path_levels(served["prog"],
                                                served["store"])
    # S0 and S2 lay out one lane a row a sweep, S1 every slot
    slots = 2 * served["mod"].nonzeros(served["cfg"])
    live = {"S0": 2 * GRID**3, "S1": slots, "S2": 2 * GRID**3}
    for name, table in zip(("S0", "S1", "S2"), case.tables):
        assert int(table["lanemask"].sum()) == live[name]


def test_counters_agree_with_the_case(served):
    case, moved = served["case"], served["moved"]
    slots = 2 * served["mod"].nonzeros(served["cfg"])
    assert moved["xla.levels"] == case.n_levels
    assert moved["xla.lanes_live"] == sum(
        int(t["lanemask"].sum()) for t in case.tables)
    assert moved["xla.lanes_padded"] == sum(
        int((t["glevel"] < case.n_levels).sum()) * t["lanemask"].shape[1]
        for t in case.tables)
    # S0 and S2 run in one slot a row a sweep
    assert moved["inspector.guarded_out"] == 2 * (slots - 2 * GRID**3)


def test_a_case_lowers_its_longest_bands_alone(served, monkeypatch):
    """Row chains give the sweep dozens of short bands; each would compile
    a nested loop of its own, so only the longest ``MAX_BANDS`` are bands
    and the rest of the levels run in the generic dispatcher."""

    from repro.compile.lowering import CompiledProgram

    case, prog = served["case"], served["prog"]

    def runs(segments, seg_dyn, kind):
        return [int(d[0]) if kind == "rec" else int(d[1] - d[0])
                for s, d in zip(segments, seg_dyn) if s[0] == kind]

    bands = runs(case.static.segments, case.seg_dyn, "rec")
    waves = runs(case.static.segments, case.seg_dyn, "wave")
    assert len(bands) == CompiledProgram.MAX_BANDS
    assert sum(bands) + sum(waves) == case.n_levels
    monkeypatch.setattr(CompiledProgram, "MAX_BANDS", 10**6)
    every = runs(*CompiledProgram._segment_levels(
        prog, case.schedule, case.n_levels, len(prog.statements)), "rec")
    assert len(every) > len(bands)
    assert sorted(bands) == sorted(every)[-len(bands):]
