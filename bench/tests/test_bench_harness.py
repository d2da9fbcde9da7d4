"""The benchmark's own tests, on the CPU at tiny sizes.

    python -m pytest bench/tests -q

Nothing here touches a TPU: the harness's look for a chip is skipped
(``require_chip=False``), and the trace reduction reads a recorded trace.
"""

from __future__ import annotations

import gzip
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import devtrace  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402
import nest  # noqa: E402

SEED = 2**35 + 17  # wider than 32 bits, as a run's seed may be

TOY_CONFIG = {
    "name": "toy",
    "source": "a first-order linear recurrence, written for this test",
    "check": {"number": "gap", "limit": 2.0**-32},
}

TOY_MODULE = '''
import numpy as np
import gen


def _body(prev, b):
    return 0.5 * prev + b


def program(cfg, sizes):
    from repro.core import PlanOptions
    from repro.core.ir import ArrayRef, LoopProgram, Statement

    return LoopProgram(
        statements=(Statement("S1", ArrayRef("a", 0),
                              (ArrayRef("a", -1), ArrayRef("b", 0)),
                              compute=_body),),
        bounds=((1, sizes["n"]),),
    ), PlanOptions()


def setup(cfg, traffic, seed):
    return {}


def request_store(cfg, traffic, shared, sizes, client, index, prev):
    n = sizes["n"]
    r = gen.rng(shared["seed"], 5, index)
    keys = [(k,) for k in range(n)]
    return {"a": dict(zip(keys, r.uniform(-1, 1, n).tolist())),
            "b": dict(zip(keys, r.uniform(-1, 1, n).tolist()))}


def reference(cfg, shared, sizes, inputs, dtype):
    a = list(inputs["a"].astype(dtype))
    b = list(inputs["b"].astype(dtype))
    half = dtype(0.5)
    for i in range(1, sizes["n"]):
        a[i] = half * a[i - 1] + b[i]
    return {"a": np.asarray(a, dtype=np.float64)}
'''


def _tiny_root(tmp_path: Path) -> Path:
    """A checkout of the benchmark with every cell cut to a test's size."""

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root)

    def edit(path: Path, **changes) -> None:
        d = json.loads(path.read_text())
        d.update(changes)
        path.write_text(json.dumps(d))

    traffic = root / "bench" / "traffic"
    edit(traffic / "medium-tsteps.json", sizes={"N": 20}, restart=3)
    edit(traffic / "small-sizes.json",
         sizes={"N": {"range": [56, 63], "warm": [63]}})
    edit(root / "bench" / "configs" / "spmv_coo.json", SCALE=5)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return _tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root: Path, cell: str, seconds: float = 1.5, trace: bool = False,
         **kw) -> dict:
    return harness.run_cell(harness.Bench(root), cell, SEED, seconds, trace,
                            t_process=0.0, require_chip=False, **kw)


# ---------------------------------------------------------------------- #
# Trace reduction
# ---------------------------------------------------------------------- #

def test_trace_reduction_on_synthetic_events():
    dev = "/device:TPU:0"
    events = [
        ("/host:CPU", "python", devtrace.WINDOW, 1000.0, 9000.0),
        (dev, devtrace.MODULES_LINE, "jit__exec(1)", 2000.0, 3000.0),
        (dev, devtrace.OPS_LINE, "fusion.1", 2000.0, 1000.0),
        (dev, devtrace.OPS_LINE, "fusion.2", 2500.0, 1000.0),   # overlaps
        (dev, devtrace.OPS_LINE, "copy.3", 6000.0, 500.0),
        (dev, devtrace.OPS_LINE, "copy.3", 9000.0, 2000.0),     # clipped
        (dev, devtrace.MODULES_LINE, "jit_other(2)", 6000.0, 500.0),
    ]
    host = [("run", 3500.0, 6000.0, 1), ("xla.to_host", 4000.0, 5000.0, 2)]
    red = devtrace.reduce(events, "_exec", host)
    # busy: [2000, 3500] + [6000, 6500] + [9000, 10000]
    assert red["busy_s"] == pytest.approx(3000e-9)
    assert red["window_s"] == pytest.approx(9000e-9)
    assert red["module_s"] == [pytest.approx(3000e-9)]
    assert red["device_ops"][0] == ["copy.3", pytest.approx(1500e-9)]
    gaps = dict(red["idle_gaps"])
    assert gaps["xla.to_host"] == pytest.approx(2500e-9)  # 3500..6000
    assert gaps["no span"] == pytest.approx(1000e-9 + 2500e-9)


def test_trace_reduction_on_a_recorded_v5e_trace():
    """The first second of a traced window of the uniform SpMV cell at SCALE 12 on a
    TPU v5 lite: a few requests' level loops and their copies."""

    with gzip.open(BENCH / "tests" / "data" / "trace_v5e.json.gz", "rt") as f:
        events = [tuple(e) for e in json.load(f)]
    red = devtrace.reduce(events, harness.LEVEL_LOOP_MODULE)
    assert red is not None and red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["module_s"], "the level loop's module was not found"
    assert sum(red["module_s"]) <= red["window_s"]
    assert len(red["device_ops"]) <= 10
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)


# ---------------------------------------------------------------------- #
# Traffic
# ---------------------------------------------------------------------- #

def test_kronecker_rows_are_far_heavier_than_uniform_ones():
    cfg = {"SCALE": 12, "edgefactor": 16,
           "initiator": {"A": 0.57, "B": 0.19, "C": 0.19}}
    row_k, col_k = gen.edges({"kind": "kronecker", "seed": 1}, cfg, SEED)
    row_u, col_u = gen.edges({"kind": "uniform", "seed": 1}, cfg, SEED)
    for row, col in ((row_k, col_k), (row_u, col_u)):
        assert row.shape == col.shape == (65536,)
        assert row.min() >= 0 and row.max() < 4096
    heavy_k = np.bincount(row_k, minlength=4096).max()
    heavy_u = np.bincount(row_u, minlength=4096).max()
    assert heavy_k > 1000 and heavy_u < 64, (heavy_k, heavy_u)


def test_size_order_uses_each_size_once_in_a_seeded_order():
    spec = {"range": [91, 127], "warm": [127]}
    a, b = gen.size_order(spec, SEED), gen.size_order(spec, SEED + 1)
    assert sorted(a) == sorted(b) == list(range(91, 127))
    assert a != b and a == gen.size_order(spec, SEED)
    # dealt in pairs (lo + k, hi - k): each pair sums to the same
    assert {a[i] + a[i + 1] for i in range(0, len(a), 2)} == {217}
    stream = gen.RequestStream({"sizes": {"N": spec}}, SEED)
    assert stream.warm_sizes() == [{"N": 127}]
    seen = [stream.next()[1]["N"] for _ in range(36)]
    assert seen == a and stream.passes() == 1
    # used up, the same sequence starts again
    assert [stream.next()[1]["N"] for _ in range(3)] == a[:3]
    assert stream.passes() == 2


def test_a_structure_added_as_a_file_is_found_by_name(tmp_path):
    (tmp_path / "structures").mkdir()
    (tmp_path / "structures" / "star.py").write_text(
        "import numpy as np\n"
        "def edges(cfg, params, r):\n"
        "    m = cfg['edgefactor'] << cfg['SCALE']\n"
        "    return np.zeros(m, np.int64), r.integers(0, 1 << cfg['SCALE'], m)\n")
    cfg = {"SCALE": 5, "edgefactor": 4}
    row, col = gen.edges({"kind": "star", "seed": 3}, cfg, SEED, tmp_path)
    assert row.shape == col.shape == (128,)
    assert len(np.unique(row)) == 1          # one hub, relabelled
    with pytest.raises(KeyError):
        gen.edges({"kind": "nowhere", "seed": 3}, cfg, SEED, tmp_path)


# ---------------------------------------------------------------------- #
# Metric arithmetic
# ---------------------------------------------------------------------- #

def _window(latencies_ms, spans=(), seconds=2.0):
    reqs = [harness.Request(0, k, {}, t0=k, t1=k + ms / 1e3)
            for k, ms in enumerate(latencies_ms)]
    return harness.Window(seconds=seconds, setup_s=3.5, requests=reqs,
                          spans=list(spans), span_requests=len(reqs),
                          device=None, nest_bytes=8.0,
                          peaks={"hbm_bytes_per_s": 8.0})


def test_metric_arithmetic_over_all_requests(tiny_root):
    bench = harness.Bench(tiny_root)
    lat = [float(v) for v in range(1, 101)]  # 1..100 ms
    spans = [("run", 0.0, 0.010, 1, None), ("xla.execute", 0.0, 0.004, 2, "run"),
             ("xla.to_device", 0.0, 0.001, 2, "run"), ("plan", 0.0, 0.002, 1, None)]
    w = _window(lat, spans, seconds=4.0)
    read = {m: bench.reader(m)(w) for m in (
        "requests_per_s", "latency_p50_ms", "latency_p90_ms", "setup_s",
        "serve_ms", "plan_ms", "store_ms", "copy_ms", "level_loop_ms",
        "tables_ms", "level_loop_device_ms", "device_idle_share")}
    assert read["requests_per_s"] == 25.0
    assert read["latency_p50_ms"] == pytest.approx(50.5)
    assert read["latency_p90_ms"] == pytest.approx(90.1)
    assert read["setup_s"] == 3.5
    assert read["level_loop_ms"] == pytest.approx(0.04)   # 4 ms / 100
    assert read["copy_ms"] == pytest.approx(0.01)
    assert read["store_ms"] == pytest.approx(0.05)        # 10 - 4 - 1
    assert read["plan_ms"] == pytest.approx(0.02)
    assert read["serve_ms"] == pytest.approx(50.5 - 0.12)
    # nothing to read: left out, never 0
    assert read["tables_ms"] is None
    assert read["level_loop_device_ms"] is None
    assert read["device_idle_share"] is None
    w.device = {"busy_s": 1.0, "window_s": 4.0, "module_s": [0.5, 1.5]}
    assert bench.reader("device_idle_share")(w) == pytest.approx(75.0)
    assert bench.reader("level_loop_device_ms")(w) == pytest.approx(1000.0)
    # 8 bytes at 8 B/s is 1 s of least time against 1 s of device time
    assert bench.reader("level_loop_roofline")(w) == pytest.approx(100.0)
    assert harness.percentile([3.0], 90) == 3.0


def test_the_sample_keeps_few_replies_and_the_largest():
    kept = harness.Sample(3, SEED)
    reqs = [harness.Request(0, k, {"N": 10 + (k == 17)}, 0.0, store={},
                            out={}) for k in range(40)]
    for r in reqs:
        kept.offer(r)
    picked = kept.requests()
    assert len(picked) == 4 and reqs[17] in picked
    held = [r for r in reqs if r.out is not None]
    assert sorted(r.index for r in held) == sorted(r.index for r in picked)


def test_spans_group_into_the_requests_inside_the_window(monkeypatch):
    from repro.obs import trace

    def ev(name, a_ms, b_ms, tid, depth, parent=None):
        return {"name": name, "ts": (a_ms * 1e6 - trace._T0_NS) / 1e3,
                "dur": (b_ms - a_ms) * 1e3, "tid": tid,
                "args": {"depth": depth, "parent": parent}}

    events = [
        ev("plan", 10, 12, 1, 1), ev("run", 12, 20, 1, 1),
        ev("xla.execute", 14, 18, 1, 2, "run"),
        ev("run", 15, 30, 2, 1),                        # ends after the window
        ev("compile.tables", 16, 22, 2, 2, "run"),
        ev("run", 21, 24, 1, 1),
    ]
    monkeypatch.setattr(trace, "events", lambda: events)
    spans, requests = harness._host_spans(10e-3, 25e-3)
    assert requests == 2
    assert sorted(s[0] for s in spans) == ["plan", "run", "run", "xla.execute"]


def test_nest_bytes_counts_each_cell_read_once_and_written_once(tiny_root):
    cell = harness.Cell(harness.Bench(tiny_root), "seidel2d.medium-tsteps", 1)
    prog, _ = cell.program({"N": 20})
    inputs = check.dense_inputs(cell.store({"N": 20}, 0, 0))
    assert nest.nest_bytes(prog, inputs) == 8 * (20 * 20 + 18 * 18)

    cell = harness.Cell(harness.Bench(tiny_root), "spmv_coo.g500-s13", 1)
    prog, _ = cell.program({})
    inputs = check.dense_inputs(cell.store({}, 0, 0))
    rows = len(np.unique(inputs["row"]))
    cols = len(np.unique(inputs["col"]))
    nnz = inputs["v"].size
    assert nest.nest_bytes(prog, inputs) == 8 * (3 * nnz + cols + 2 * rows)


# ---------------------------------------------------------------------- #
# Whole runs at a test's size
# ---------------------------------------------------------------------- #

def test_a_cell_added_as_data_only_runs(tmp_path):
    root = _tiny_root(tmp_path)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    (root / "bench" / "configs" / "toy.py").write_text(TOY_MODULE)
    (root / "bench" / "traffic" / "fixed-64.json").write_text(
        json.dumps({"clients": 2, "sizes": {"n": 64}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": TOY_CONFIG["source"],
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "toy.fixed-64", "config": "toy",
                              "traffic": "fixed-64", "chips": 1,
                              "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    r = _run(root, "toy.fixed-64")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"requests_per_s", "latency_p50_ms", "setup_s"}
    assert list(r)[-1] == "checks" and r["checks"]["checked"]["value"] >= 1
    traced = _run(root, "toy.fixed-64", trace=True)
    assert traced["correct"]
    assert {"serve_ms", "store_ms", "copy_ms", "level_loop_ms"} <= set(
        traced["metrics"])


PACED_DRIVER = '''
import threading, time
import harness


def workers(traffic):
    return 1


def warm(cell):
    sizes = dict(cell.traffic["sizes"])
    return [(sizes, cell.store(sizes, 0, 10**9))]


def serve(cell, svc, seconds, log, on_start=None):
    sizes = dict(cell.traffic["sizes"])
    start = time.perf_counter()
    end = start + seconds

    def sender():
        k = 0
        while time.perf_counter() < end:
            log.add(harness.send(cell, svc, 0, k, sizes, cell.store(sizes, 0, k)))
            k += 1
            time.sleep(cell.traffic["interval_s"])

    t = threading.Thread(target=sender)
    t.start()

    def join():
        t.join()
        return {"paced": True}

    return harness.Load(start, end, join)
'''


def test_a_mix_with_a_driver_of_its_own_runs(tmp_path):
    """Another arrival process is added as files only: a driver module and
    a traffic file that names it."""

    root = _tiny_root(tmp_path)
    (root / "bench" / "drivers" / "paced.py").write_text(PACED_DRIVER)
    (root / "bench" / "traffic" / "paced-20.json").write_text(json.dumps(
        {"driver": "paced", "interval_s": 0.01, "sizes": {"N": 20}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "seidel2d.paced-20",
                              "config": "seidel2d", "traffic": "paced-20",
                              "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    logged = []
    r = _run(root, "seidel2d.paced-20", log=lambda **kw: logged.append(kw))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert logged[0]["run"]["paced"] is True
    assert logged[0]["run"]["xla_traces_in_window"] == 0


CELLS = ["seidel2d.medium-tsteps", "seidel2d.small-sizes",
         "spmv_coo.g500-s13", "spmv_coo.uniform-s13"]


@pytest.mark.parametrize("cell", CELLS)
def test_cells_run_correct_at_a_tiny_size(tiny_root, cell):
    r = _run(tiny_root, cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["gap"]["value"] <= r["checks"]["gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_the_programs_place_reads_not_correct(tiny_root, cell):
    """The reference in float32 put in the program's place: the harness's
    own decision reads it as not correct, while the program's replies to
    the same requests hold the limit."""

    r = _run(tiny_root, cell, control=np.float32)
    assert r["correct"] is False
    assert r["checks"]["gap"]["value"] > r["checks"]["gap"]["limit"]
    assert r["checks"]["program_gap"]["value"] <= r["checks"]["gap"]["limit"]


def _unchanged(run):
    def broken(self, store=None, stalls=None):
        return {a: dict(c) for a, c in store.items()}
    return broken


def _altered(run):
    def broken(self, store=None, stalls=None):
        out = run(self, store=store, stalls=stalls)
        written = self.plan.program.statements[0].write.array
        cells = out[written]
        k = sorted(cells)[len(cells) // 2]
        cells[k] = cells[k] + 1.0
        return out
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _altered],
                         ids=["state_unchanged", "answer_altered"])
@pytest.mark.parametrize("cell", ["seidel2d.medium-tsteps", "spmv_coo.g500-s13"])
def test_a_broken_timed_path_reads_not_correct(tiny_root, monkeypatch, cell,
                                                fault):
    from repro.core.parallelizer import Executable

    monkeypatch.setattr(Executable, "run", fault(Executable.run))
    r = _run(tiny_root, cell)
    assert r["correct"] is False
    assert r["checks"]["gap"]["value"] > r["checks"]["gap"]["limit"]
