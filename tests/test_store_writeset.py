"""The compiled backends convert back only the arrays a program writes.

A run can change nothing but its write set (``{s.write.array}`` over the
program's statements; index arrays are never written), so ``xla`` and
``xla_spmd`` copy only those arrays back from the device and convert only
those back to dicts.  Every read-only array comes back as the caller's own
cells, not through a round trip.  The reply still holds every array of the
store.  Checked here on the CPU:

* the SpMV shape ``y[row[k]] += v[k] * x[col[k]]`` under ``deps="inspect"``:
  read-only arrays (index arrays among them) equal the input exactly, the
  written one equals the sequential oracle;
* a sparse read-only array keeps exactly its input cells;
* ``store.passthrough_cells`` counts the read-only cells, the
  ``store.to_dicts`` span's ``cells`` the converted ones;
* the ``speculate`` rollback path returns every array the same way;
* the write set stays out of the trace identity: a warm run traces nothing.
"""

from __future__ import annotations

import pytest

import repro.obs as obs
from repro.obs import metrics, trace
from repro.core import (
    PlanOptions,
    histogram,
    indexed_store,
    paper_alg6,
    plan,
    run_sequential,
    sparse_matvec,
)
from repro.compile import run_xla

N = 16
ROWS = [k % 5 for k in range(N)]
COLS = [(3 * k) % N for k in range(N)]
BACKENDS = ("xla", "xla_spmd")


def _copy(store):
    return {a: dict(c) for a, c in store.items()}


def _spmv_store():
    prog = sparse_matvec(N)
    return prog, _copy(indexed_store(prog, {"row": ROWS, "col": COLS}))


def _read_only(prog, store):
    written = {s.write.array for s in prog.statements}
    return [a for a in store if a not in written]


def _passthrough():
    return metrics.counter("store.passthrough_cells").value


def _assert_exact(out, store, arrays):
    for a in arrays:
        # same cells in the same order, the same values of the same type
        assert list(out[a].items()) == list(store[a].items()), a
        assert all(type(v) is float for v in out[a].values()), a


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset_all()
    yield
    trace.disable()
    obs.reset_all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_spmv_reply_holds_every_array(backend):
    prog, store = _spmv_store()
    out = plan(prog, PlanOptions(deps="inspect")).compile(backend).run(
        store=_copy(store)
    )
    assert list(out) == list(store)
    read_only = _read_only(prog, store)
    assert sorted(read_only) == ["col", "row", "v", "x"]
    _assert_exact(out, store, read_only)
    # the index arrays' subscripts are integer-valued floats, kept as such
    assert [out["row"][(k,)] for k in range(N)] == [float(r) for r in ROWS]
    assert [out["col"][(k,)] for k in range(N)] == [float(c) for c in COLS]
    assert out["y"] == run_sequential(prog, store)["y"]
    assert out == run_sequential(prog, store)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sparse_read_only_array_keeps_its_cells(backend):
    prog, store = _spmv_store()
    # x holes inside its bounding box, at cells no col entry reaches
    lo, hi = min(store["x"]), max(store["x"])
    reached = {(c,) for c in COLS}
    holes = [k for k in store["x"] if lo < k < hi and k not in reached][:3]
    assert holes
    for k in holes:
        del store["x"][k]
    out = plan(prog, PlanOptions(deps="inspect")).compile(backend).run(
        store=_copy(store)
    )
    _assert_exact(out, store, ["x"])
    assert not any(k in out["x"] for k in holes)
    assert out == run_sequential(prog, store)


def test_passthrough_counts_read_only_cells():
    prog, store = _spmv_store()
    exe = plan(prog, PlanOptions(deps="inspect")).compile("xla")
    before = _passthrough()
    exe.run(store=_copy(store))
    assert _passthrough() - before == sum(
        len(store[a]) for a in _read_only(prog, store)
    )


def test_passthrough_is_zero_when_every_array_is_written():
    prog = paper_alg6(8)
    assert not _read_only(prog, prog.initial_store())
    before = _passthrough()
    out = plan(prog, method="isd").compile("xla").run()
    assert _passthrough() == before
    assert out == run_sequential(prog)


def test_to_dicts_span_counts_the_written_cells():
    prog, store = _spmv_store()
    exe = plan(prog, PlanOptions(deps="inspect")).compile("xla")
    with trace.tracing():
        exe.run(store=_copy(store))
    (span,) = [e for e in trace.events() if e["name"] == "store.to_dicts"]
    assert span["args"]["arrays"] == "y"
    assert span["args"]["cells"] == len(store["y"])


def test_speculate_rollback_returns_every_array():
    prog = histogram(8)
    store = _copy(indexed_store(prog, {"bin": [4] * 8}))  # forced conflicts
    out = plan(prog, PlanOptions(deps="speculate")).compile("xla").run(
        store=_copy(store)
    )
    assert metrics.counter("speculation.rollbacks").value == 1
    assert list(out) == list(store)
    _assert_exact(out, store, _read_only(prog, store))
    assert out == run_sequential(prog, store)


def test_warm_run_traces_nothing():
    prog, store = _spmv_store()
    exe = plan(prog, PlanOptions(deps="inspect")).compile("xla")
    exe.run(store=_copy(store))
    traces = metrics.counter("xla.traces").value
    out = exe.run(store=_copy(store))
    assert metrics.counter("xla.traces").value == traces
    assert out == run_sequential(prog, store)


def test_run_xla_compares_the_whole_store():
    prog, store = _spmv_store()
    sync = plan(prog, PlanOptions(deps="inspect")).optimized_sync
    report = run_xla(sync, store=_copy(store), deps="inspect")
    assert report.matches_sequential
    assert list(report.store) == list(store)
    _assert_exact(report.store, store, _read_only(prog, store))
