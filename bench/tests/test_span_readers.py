"""The readers of the program's request spans (``queue_ms``,
``store_convert_ms``), on the CPU at tiny sizes.

    python -m pytest bench/tests -q

Each test serves a short request stream of a cell cut to a test's size
through ``PlanService`` with tracing on, groups the spans into requests as
the harness does, and reads the metrics from them.
"""

from __future__ import annotations

import json
import shutil
import sys
import threading
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import gen  # noqa: E402
import harness  # noqa: E402

SEED = 2**35 + 29  # wider than 32 bits, as a run's seed may be
REQUESTS_PER_CLIENT = 4


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout of the benchmark with the two cells cut to a test's
    size."""

    root = tmp_path_factory.mktemp("bench") / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for path, changes in (
        (root / "bench" / "traffic" / "medium-tsteps.json", {"sizes": {"N": 20}}),
        (root / "bench" / "configs" / "spmv_coo.json", {"SCALE": 5}),
    ):
        d = json.loads(path.read_text())
        d.update(changes)
        path.write_text(json.dumps(d))
    return root


def _serve_traced(bench: harness.Bench, name: str):
    """Every client of the cell sends REQUESTS_PER_CLIENT requests, warm,
    with tracing on; returns the requests and the window around them."""

    from repro.obs import trace
    from repro.serve import PlanService, ServiceOptions

    cell = harness.Cell(bench, name, SEED)
    driver = bench.driver(cell.traffic)
    clients = driver.workers(cell.traffic)
    stream = gen.RequestStream(cell.traffic, SEED)
    served, lock = [], threading.Lock()

    def client(c: int) -> None:
        for _ in range(REQUESTS_PER_CLIENT):
            k, sizes = stream.next()
            req = harness.send(cell, svc, c, k, sizes, cell.store(sizes, c, k))
            with lock:
                served.append(req)

    svc = PlanService(ServiceOptions(backend="xla", workers=clients))
    try:
        for sizes, store in driver.warm(cell):
            prog, options = cell.program(sizes)
            svc.submit(prog, options, store=store, run=True).result()
        trace.clear()
        trace.enable()
        t_lo = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        t_hi = time.perf_counter()
    finally:
        trace.disable()
        svc.close()
    return served, t_lo, t_hi


@pytest.mark.parametrize("name", ["seidel2d.medium-tsteps",
                                  "spmv_coo.uniform-s13"])
def test_request_span_readers_cover_every_request(tiny_root, name):
    bench = harness.Bench(tiny_root)
    served, t_lo, t_hi = _serve_traced(bench, name)
    assert [r.error for r in served] == [None] * len(served)

    spans, span_requests = harness._host_spans(t_lo, t_hi)
    assert span_requests == len(served)

    window = harness.Window(
        seconds=t_hi - t_lo, setup_s=0.0, requests=served, spans=spans,
        span_requests=span_requests, device=None, nest_bytes=0.0, peaks={},
    )
    mean_latency = sum(r.latency_ms for r in served) / len(served)
    queue = bench.reader("queue_ms")(window)
    convert = bench.reader("store_convert_ms")(window)
    assert queue is not None and convert is not None
    assert 0 <= queue <= mean_latency
    assert 0 < convert <= mean_latency
