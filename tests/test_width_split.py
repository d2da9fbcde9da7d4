"""Width-split band lowering (ROADMAP 3b, :mod:`repro.compile.lowering`).

A recurrence band's ramp-up/ramp-down levels run at sliced lane widths (the
"width ladder"), so a skewed diamond stops paying the plateau's padded lane
count on every level.  Contracts:

* **Bit-equality** — split and unsplit lowerings produce identical stores,
  and both match the sequential oracle across elimination methods (the
  sliced-away lanes are masked padding, so this is structural).
* **Degenerate bands stay byte-identical** — a uniform band (every row as
  wide as the plateau) appends no cut points: its dynamic vector, and
  therefore its trace, is exactly yesterday's.
* **Bucket identity survives** — the ladder depth is derived from the
  dynamic vector's *shape* (a bucket component), so bounds sharing a bucket
  still share one trace (PR 8's zero-re-trace property).
* **SPMD opts out** — the sharded artifact's per-shard lane slicing needs
  full padded widths; its ``_band_rungs`` hook pins the ladder off.
"""

from __future__ import annotations

import numpy as np
import pytest

import repro.obs as obs
from repro.core import (
    ArrayRef,
    LoopProgram,
    Statement,
    analyze,
    insert_synchronization,
)
from repro.core.wavefront import _DenseStore
from repro.compile import lowering
from repro.compile.cache import CompileCache
from repro.compile.executor import run_xla

from oracle import assert_equivalent


@pytest.fixture(autouse=True)
def _clean():
    obs.reset_all()
    yield
    obs.reset_all()


def _serialized_skew(ni, nj):
    """One statement carrying {(0,1), (1,-1)} — skewed into a diagonal
    wavefront whose band widths ramp 1, 2, … up to the plateau and back."""

    return LoopProgram(
        statements=(
            Statement(
                "S1",
                ArrayRef("a", (0, 0)),
                (ArrayRef("a", (0, -1)), ArrayRef("a", (-1, 1))),
            ),
        ),
        bounds=((0, ni), (0, nj)),
    )


def _prepare(prog, scc_policy, cache=None):
    sync = insert_synchronization(prog, analyze(prog))
    store = prog.initial_store()
    cache = cache if cache is not None else CompileCache()
    rep = run_xla(
        sync, cache=cache, scc_policy=scc_policy, compare=False, store=store
    )
    dense = _DenseStore({a: dict(c) for a, c in store.items()})
    case, _ = rep.compiled.prepare(sync.program, dense)
    return rep, case


def _rec_dyns(case):
    return [
        dyn
        for seg, dyn in zip(case.static.segments or (), case.seg_dyn)
        if seg[0] == "rec"
    ]


# ---------------------------------------------------------------------- #
# The ladder itself
# ---------------------------------------------------------------------- #

def test_ladder_cuts_are_monotone_and_fit_the_ramp():
    rep, case = _prepare(_serialized_skew(48, 96), "skew")
    assert rep.matches_sequential
    (dyn,) = _rec_dyns(case)
    (seg,) = [s for s in case.static.segments if s[0] == "rec"]
    n_stmts = len(seg[1])
    L = (dyn.shape[0] - 1 - n_stmts) // 2
    wpb = max(case.tables[k]["lanemask"].shape[1] for k in seg[1])
    # the full ladder: every cap from WIDTH_LADDER_MIN lanes up
    assert L == rep.compiled._band_rungs(wpb) > 0
    assert lowering._ladder(wpb, L)[0] == lowering.WIDTH_LADDER_MIN
    n = int(dyn[0])
    cuts = [int(c) for c in dyn[1 + n_stmts:]]
    # monotone: 0 <= P_1 <= ... <= P_L <= Q_L <= ... <= Q_1 <= n
    assert all(a <= b for a, b in zip([0] + cuts, cuts + [n]))
    # narrowest rung holds at least WIDTH_LADDER_MIN lanes
    assert cuts[0] > 0 and cuts[-1] < n


def test_split_bit_equal_to_unsplit_and_oracle(monkeypatch):
    prog = _serialized_skew(40, 80)
    rep_split, case_split = _prepare(prog, "skew")
    assert _rec_dyns(case_split)[0].shape[0] > 2  # ladder engaged

    monkeypatch.setattr(lowering, "WIDTH_LADDER_RUNGS", 0)
    rep_unsplit, case_unsplit = _prepare(prog, "skew")
    assert _rec_dyns(case_unsplit)[0].shape[0] == 2  # [run, row0]
    monkeypatch.undo()

    assert rep_split.matches_sequential
    assert rep_unsplit.matches_sequential
    assert rep_split.store == rep_unsplit.store


def test_full_corpus_equivalence_with_ladder_active():
    """The canonical differential harness over programs whose bands ramp —
    every registered backend, naive and optimized sync, bit-for-bit."""

    assert_equivalent(_serialized_skew(20, 40), threaded=False)
    # mixed-sign diagonal recurrence (chunked ramp + tail)
    assert_equivalent(
        LoopProgram(
            statements=(
                Statement(
                    "S1", ArrayRef("a", (0, 0)), (ArrayRef("a", (-1, 1)),)
                ),
            ),
            bounds=((0, 24), (0, 12)),
        ),
        threaded=False,
    )


def test_uniform_band_appends_no_cuts():
    """A chunked DOACROSS whose chunks all fill the padded width exactly —
    the dynamic vector (hence the trace) must be byte-identical to the
    pre-ladder lowering."""

    # mixed-sign (1,-1) over 15×16: chunk 15 tiles the 240 iterations into
    # 16 equal rows — every row as wide as the plateau, nothing to split
    prog = LoopProgram(
        statements=(
            Statement("S1", ArrayRef("a", (0, 0)), (ArrayRef("a", (-1, 1)),)),
        ),
        bounds=((0, 15), (0, 16)),
    )
    sync = insert_synchronization(prog, analyze(prog))
    store = prog.initial_store()
    cache = CompileCache()
    rep = run_xla(
        sync, cache=cache, scc_policy="chunk", compare=False, store=store
    )
    dense = _DenseStore({a: dict(c) for a, c in store.items()})
    case, _ = rep.compiled.prepare(sync.program, dense)
    (dyn,) = _rec_dyns(case)
    (seg,) = [s for s in case.static.segments if s[0] == "rec"]
    assert dyn.shape[0] == 1 + len(seg[1])  # [run, row bases] — no cuts


def test_bucket_identity_and_zero_retrace_with_ladder():
    cache = CompileCache()
    prog_a = _serialized_skew(48, 96)
    rep_a, _ = _prepare(prog_a, "skew", cache=cache)
    comp = rep_a.compiled
    assert comp.trace_count == 1
    # same bucket (47/95 pad to the same shapes): tables rebuild, trace
    # does not
    prog_b = _serialized_skew(47, 95)
    sync_b = insert_synchronization(prog_b, analyze(prog_b))
    rep_b = run_xla(
        sync_b,
        cache=cache,
        scc_policy="skew",
        compare=False,
        store=prog_b.initial_store(),
    )
    assert rep_b.compiled is comp
    assert comp.trace_count == 1
    assert comp.bucket_count == 1


def test_spmd_pins_the_ladder_off():
    from repro.compile.spmd import SpmdCompiledProgram

    assert SpmdCompiledProgram._band_rungs(object(), 4096) == 0
    # the base artifact ladders the same width: caps 8, 32, 128, 512, 2048
    assert lowering.CompiledProgram._band_rungs(object(), 4096) == 5


def test_lane_cap_never_exceeds_statement_width():
    """Multi-statement band shapes: a statement narrower than the band
    plateau is never sliced below its own padded width (the cut search
    clamps per statement)."""

    rep, case = _prepare(_serialized_skew(16, 128), "skew")
    assert rep.matches_sequential
    for seg, dyn in zip(case.static.segments, case.seg_dyn):
        if seg[0] != "rec":
            continue
        n_stmts = len(seg[1])
        L = (dyn.shape[0] - 1 - n_stmts) // 2
        wpb = max(
            case.tables[k]["lanemask"].shape[1] for k in seg[1]
        )
        for cap in lowering._ladder(wpb, L):
            assert lowering.WIDTH_LADDER_MIN <= cap <= wpb
