"""Lane caps of the level loop (:mod:`repro.compile.lowering`).

Every table row runs at the narrowest cap of the width ladder that holds its
live lanes: a recurrence band's rows at its ramps' caps, a wave statement's
row at the cap its ``rung`` column names (a ``lax.switch`` over the caps,
inside the statement's ``lax.cond``).  Contracts:

* **Bit-equality** — the deep ladder, the 3-rung ladder and no ladder
  (``WIDTH_LADDER_RUNGS = 0``) give the same store, and it is the
  sequential oracle's: the lanes a cap leaves out are masked padding.
* **Narrow rows run narrow** — a wave row with ≤ 8 live lanes in a
  4,096-lane statement runs at ``WIDTH_LADDER_MIN`` lanes.
* **Bucket identity survives** — the switch's branch count is a function
  of the padded width, so two bounds in one bucket share one trace.
* **The counter is the count** — ``xla.lanes_computed`` is the sum of the
  caps the run computes, walked level by level from the case's rows.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.obs as obs
from repro.compile import lowering
from repro.compile.cache import CompileCache
from repro.compile.executor import run_xla
from repro.core import analyze, insert_synchronization
from repro.core.inspector import indexed_store
from repro.core.ir import ArrayRef, IndirectRef, LoopProgram, Statement
from repro.core.wavefront import _DenseStore
from repro.obs import metrics

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import gen  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    obs.reset_all()
    yield
    obs.reset_all()


def _histogram(hot: int, narrow: int = 5, repeats: int = 2):
    """``h[bin[k]] = h[bin[k]] + w[k]`` over ``hot`` distinct bins, then
    ``repeats`` more rounds over the first ``narrow`` bins: one wide level,
    then ``repeats`` levels of ``narrow`` lanes — too few for a band, so
    every level runs in the generic dispatcher."""

    h = IndirectRef("h", ArrayRef("bin", 0))
    n = hot + narrow * repeats
    prog = LoopProgram(
        statements=(Statement("S1", h, (h, ArrayRef("w", 0))),),
        bounds=((0, n),),
    )
    bins = list(range(hot)) + list(range(narrow)) * repeats
    return prog, indexed_store(prog, {"bin": bins})


def _spmv(scale: int):
    """The benchmark's Kronecker COO SpMV at ``scale``: one band of falling
    widths, one nonzero of each row a level."""

    cfg = json.loads((BENCH / "configs" / "spmv_coo.json").read_text())
    cfg["SCALE"] = scale
    mod = gen.load_module(BENCH / "configs" / "spmv_coo.py", "cfg_spmv")
    traffic = {"structure": {"kind": "kronecker", "seed": 1}}
    seed = 2**33 + 3
    shared = dict(mod.setup(cfg, traffic, seed), seed=seed)
    prog, _options = mod.program(cfg, {})
    return prog, mod.request_store(cfg, traffic, shared, {}, 0, 0, None)


def _symgs(grid: int):
    from test_hpcg_symgs import symgs

    _mod, _cfg, prog, _options, store = symgs(grid)
    return prog, store


def _run(prog, store, cache=None):
    """Serve ``prog`` once under ``deps="inspect"`` (against the sequential
    oracle) and return the report with the case it ran."""

    sync = insert_synchronization(prog, analyze(prog))
    rep = run_xla(
        sync,
        store={a: dict(c) for a, c in store.items()},
        cache=cache if cache is not None else CompileCache(),
        deps="inspect",
    )
    case, hit = rep.compiled.prepare(prog, _DenseStore(store))
    assert hit
    return rep, case


def _walk_lanes(case) -> int:
    """The lanes a run of ``case`` computes, level by level: a band level
    runs each band statement at its rung's cap (found from the band's cut
    points), a wave level runs each active cond statement at the narrowest
    ladder cap holding the row's live lanes and each condless statement at
    its full width."""

    widths = [t["lanemask"].shape[1] for t in case.tables]
    live = [t["lanemask"].sum(axis=1) for t in case.tables]
    glevel = [t["glevel"] for t in case.tables]
    band_at = {}  # level -> (statements, cap)
    level = 0
    for seg, dyn in zip(case.static.segments or (), case.seg_dyn):
        if seg[0] == "wave":
            level = int(dyn[1])
            continue
        ks = seg[1]
        n, cuts = int(dyn[0]), [int(c) for c in dyn[1 + len(ks):]]
        wpb = max(widths[k] for k in ks)
        ws = lowering._ladder(wpb, len(cuts) // 2)
        caps = ws + ws[-2::-1]
        edges = [0] + cuts + [n]
        for t in range(n):
            rung = max(i for i in range(len(caps)) if edges[i] <= t)
            band_at[level + t] = (ks, caps[rung])
        level += n
    total = 0
    for level in range(case.n_levels):
        if level in band_at:
            ks, cap = band_at[level]
            total += sum(min(cap, widths[k]) for k in ks)
            continue
        for k, ss in enumerate(case.static.stmts):
            if not ss.use_cond:
                total += widths[k]
                continue
            rows = np.flatnonzero(glevel[k] == level)
            if rows.size:
                caps = lowering._ladder(widths[k], ss.lane_rungs)
                total += min(c for c in caps if c >= live[k][rows[0]])
    return total


# ---------------------------------------------------------------------- #
# Bit-equality across ladders
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize(
    "build", [lambda: _spmv(8), lambda: _symgs(8)], ids=["spmv_s8", "symgs_8"]
)
def test_stores_bit_equal_across_ladders(build, monkeypatch):
    prog, store = build()
    stores, computed = [], []
    for rungs in (lowering.WIDTH_LADDER_RUNGS, 3, 0):
        monkeypatch.setattr(lowering, "WIDTH_LADDER_RUNGS", rungs)
        rep, case = _run(prog, store)
        assert rep.matches_sequential
        stores.append(rep.store)
        computed.append(case.lanes_computed)
    assert stores[0] == stores[1] == stores[2]
    # a deeper ladder computes fewer lanes, never more
    assert computed[0] <= computed[1] < computed[2]


def test_symgs_wave_rows_run_at_their_caps():
    """At 8^3 the sweep's wide guarded statement runs in the waves as well
    as in bands, and its narrow wave rows drop to the narrowest cap."""

    prog, store = _symgs(8)
    rep, case = _run(prog, store)
    assert rep.matches_sequential
    kinds = [s[0] for s in case.static.segments]
    assert "wave" in kinds and "rec" in kinds
    switched = [
        (ss, t) for ss, t in zip(case.static.stmts, case.tables)
        if ss.use_cond and ss.lane_rungs > 0
    ]
    assert switched
    for ss, t in switched:
        rows = t["glevel"] < case.n_levels
        live = t["lanemask"][rows].sum(axis=1)
        caps = np.asarray(lowering._ladder(t["lanemask"].shape[1],
                                           ss.lane_rungs))
        got = caps[t["rung"][rows]]
        assert (got >= live).all()
        # the narrowest cap that holds the live lanes
        narrower = np.where(t["rung"][rows] > 0,
                            caps[np.maximum(t["rung"][rows] - 1, 0)], 0)
        assert (narrower < live).all()


def test_narrow_wave_row_of_a_wide_statement_runs_at_cap_8():
    prog, store = _histogram(4000)
    rep, case = _run(prog, store)
    assert rep.matches_sequential
    assert [s[0] for s in case.static.segments] == ["wave"]
    (ss,), (t,) = case.static.stmts, case.tables
    assert ss.use_cond and t["lanemask"].shape[1] == 4096
    assert list(t["lanemask"].sum(axis=1)[:3]) == [4000, 5, 5]
    caps = lowering._ladder(4096, ss.lane_rungs)
    assert caps[0] == lowering.WIDTH_LADDER_MIN == 8
    assert [caps[r] for r in t["rung"][:3]] == [4096, 8, 8]
    assert case.lanes_computed == 4096 + 8 + 8


def test_two_bounds_in_one_bucket_share_one_trace():
    cache = CompileCache()
    rep_a, case_a = _run(*_histogram(4000), cache=cache)
    comp = rep_a.compiled
    assert comp.trace_count == 1
    rep_b, case_b = _run(*_histogram(3000), cache=cache)
    assert rep_b.compiled is comp and rep_b.matches_sequential
    assert case_b.bucket == case_a.bucket
    assert comp.trace_count == 1
    assert comp.bucket_count == 1


# ---------------------------------------------------------------------- #
# The ladder itself
# ---------------------------------------------------------------------- #

def test_cuts_stay_monotone_at_depth_10():
    """A diamond band whose rows ramp from 1 lane up to 2^22 and back: ten
    rungs below the plateau, every cut monotone and every row within its
    rung's cap."""

    wpb = 1 << 22
    comp = lowering.CompiledProgram.__new__(lowering.CompiledProgram)
    rungs = comp._band_rungs(wpb)
    assert rungs == 10
    up = [1, 3, 8, 9, 40, 100, 500, 513, 2000, 9000, 40000, 130000,
          600000, 1 << 20, 3_000_000, wpb]
    widths = up + [wpb] * 3 + up[::-1]
    (dyn,) = comp._split_band_widths(
        (("rec", (0,)),),
        (np.asarray([len(widths), 0], np.int32),), [widths], [wpb],
    )
    cuts = [int(c) for c in dyn[2:]]
    assert len(cuts) == 2 * rungs
    n = len(widths)
    assert all(a <= b for a, b in zip([0] + cuts, cuts + [n]))
    ws = lowering._ladder(wpb, rungs)
    caps = ws + ws[-2::-1]
    edges = [0] + cuts + [n]
    for lo, hi, cap in zip(edges, edges[1:], caps):
        assert all(w <= cap for w in widths[lo:hi])


# ---------------------------------------------------------------------- #
# The xla.lanes_computed counter
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize(
    "build",
    [lambda: _spmv(8), lambda: _symgs(8), lambda: _histogram(4000)],
    ids=["spmv_s8", "symgs_8", "histogram"],
)
def test_lanes_computed_counter_is_the_walked_count(build):
    prog, store = build()
    before = metrics.counter("xla.lanes_computed").value
    rep, case = _run(prog, store)
    moved = metrics.counter("xla.lanes_computed").value - before
    assert moved == case.lanes_computed == _walk_lanes(case)
    assert case.lanes_live <= case.lanes_computed <= case.lanes_padded


def test_g500_s13_computes_its_falling_band_at_the_ladder_caps():
    """The benchmark's ``spmv_coo.g500-s13`` case at full SCALE 13 (tables
    only, no run): one band of 3,687 rows whose widths fall from 8,192 to
    1; with caps 8, 32, …, 2048 and 8192 it computes 263,664 lanes, against
    30,203,904 padded."""

    from repro.compile import clear_compile_cache
    from repro.core import PlanOptions, plan

    prog, store = _spmv(13)
    try:
        compiled = plan(prog, PlanOptions(deps="inspect")).compile(
            "xla"
        ).compiled
        case, _ = compiled.prepare(prog, _DenseStore(store))
    finally:
        clear_compile_cache()  # the tables hold ~0.5 GB
    assert case.n_levels == 3687
    assert [s[0] for s in case.static.segments] == ["rec"]
    assert case.lanes_padded == 30_203_904
    assert case.lanes_computed == _walk_lanes(case) == 263_664
