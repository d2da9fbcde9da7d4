"""The benchmark's harness: finds a cell's configuration, traffic and metric
readers by name, serves the cell's traffic through ``PlanService`` for a
fixed window, and checks what the window served against the plain
reference.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under the benchmark's directory, found by the name
``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` (named by the configuration's ``file``) and the
  module beside it, ``configs/<config>.py``: ``program(cfg, sizes)``,
  ``setup(cfg, traffic, seed)``, ``request_store(...)`` and the plain
  ``reference(cfg, shared, sizes, inputs, dtype)``;
* ``traffic/<traffic>.json``, read by :mod:`gen`, and the driver it names,
  ``drivers/<driver>.py`` (``closed`` by default), which sends the
  requests; a structure it names is ``structures/<kind>.py``;
* ``metrics/<metric>.py`` with ``read(window) -> float | None``; ``None``
  leaves the metric out of the result.
"""

from __future__ import annotations

import dataclasses
import json
import shutil
import statistics
import sys
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import devtrace  # noqa: E402
import gen  # noqa: E402
import nest  # noqa: E402

# the level loop's jit, as the profiler names its module
LEVEL_LOOP_MODULE = "_exec"
# the profiled part of a traced window: its last seconds (host spans cover
# all of it)
PROFILE_SECONDS = 10.0
# replies the check compares: this many drawn from the seed, and the largest
CHECK_SAMPLE = 6


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def percentile(values: List[float], p: float) -> float:
    """The ``p``-th percentile of all values, linear between closest ranks
    (``statistics.quantiles(..., method="inclusive")``)."""

    if len(values) == 1:
        return float(values[0])
    return float(
        statistics.quantiles(values, n=100, method="inclusive")[int(p) - 1]
    )


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: Path) -> None:
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.spec["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Tuple[dict, object]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                path = self.root / c["file"]
                cfg = json.loads(path.read_text())
                return cfg, gen.load_module(path.with_suffix(".py"),
                                            f"cfg_{name}")
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""

        return [
            m for m in self.spec[kind]
            if cell in m.get("workloads", [cell])
        ]

    def reader(self, metric: str) -> Callable:
        return gen.plugin("metrics", metric, self.dir).read

    def driver(self, traffic: dict):
        return gen.plugin("drivers", traffic.get("driver", "closed"),
                          self.dir)

    def peaks(self, kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if kind not in table["kinds"]:
            raise KeyError(
                f"device kind {kind!r} is not in peaks.json "
                f"({sorted(table['kinds'])})"
            )
        return table["kinds"][kind]


@dataclasses.dataclass(eq=False)
class Request:
    client: int
    index: int
    sizes: Dict[str, int]
    t0: float
    t1: float = 0.0
    store: Optional[dict] = None     # the input store
    out: Optional[dict] = None       # the arrays the program writes
    error: Optional[str] = None

    @property
    def latency_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


@dataclasses.dataclass
class Window:
    """What a metric reader reads: the requests completed in the window,
    the host spans in it, the device trace's reduction, and the
    shapes' bytes."""

    seconds: float
    setup_s: float
    requests: List[Request]
    spans: List[Tuple]   # name, start s, end s, depth, parent
    span_requests: int   # the requests those spans cover
    device: Optional[dict]
    nest_bytes: float                            # mean per request
    peaks: dict


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform == "cpu":
        raise NoChip(f"no accelerator: JAX platform {devices[0].platform!r}")
    if require_chip and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX sees "
                     f"{len(devices)}")
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def pin_profile(bench_dir: Path, kind: str) -> dict:
    """Install the cost profile measured once on this kind of chip, so the
    strategy auction prices with the same units in every run."""

    from repro import calibrate

    path = bench_dir / "profile" / "cost_profile.json"
    pinned = json.loads(path.read_text())
    if kind is not None and kind not in pinned["device_kinds"]:
        raise KeyError(f"the pinned cost profile was measured on "
                       f"{pinned['device_kinds']}, not {kind!r}")
    calibrate.set_profile(calibrate.CostProfile(
        units=dict(pinned["units"]),
        fingerprint=calibrate.host_fingerprint(),
        generation=1,
        source="persisted",
        meta={"pinned": str(path)},
    ))
    return pinned["units"]


def describe_case(executable, prog, store) -> dict:
    """Strategy, level count and padded lanes the served plan runs."""

    from repro.core.wavefront import _DenseStore

    case, _ = executable.compiled.prepare(prog, _DenseStore(store))
    sched = case.schedule
    strategies = [r.strategy for r in sched.scc.recurrences] if sched.scc else []
    return {
        "strategy": strategies or ["layer"],
        "levels": case.n_levels,
        "instances": sched.instances,
        "padded_lanes": [int(t["lanemask"].shape[1]) for t in case.tables],
        "padded_groups": [int(t["lanemask"].shape[0]) for t in case.tables],
    }


def _host_spans(t_lo: float, t_hi: float) -> Tuple[List[Tuple], int]:
    """The program's spans of the requests served inside [t_lo, t_hi], as
    (name, start, end, depth, parent name) in perf_counter seconds, and the
    number of those requests.

    A worker thread serves one request at a time, and each request's spans
    end with its top-level ``run``: taken in the order they end, a thread's
    spans up to and including a ``run`` are one request's.  A request
    counts when all of its spans lie inside the window."""

    from repro.obs import trace

    by_thread: Dict[int, List[Tuple]] = {}
    for ev in trace.events():
        a = (ev["ts"] * 1e3 + trace._T0_NS) * 1e-9
        args = ev["args"]
        by_thread.setdefault(ev["tid"], []).append(
            (ev["name"], a, a + ev["dur"] * 1e-6, int(args.get("depth", 0)),
             args.get("parent")))
    out, requests = [], 0
    for spans in by_thread.values():
        pending: List[Tuple] = []
        for span in sorted(spans, key=lambda sp: sp[2]):
            pending.append(span)
            if span[0] == "run" and span[3] == 1:
                if min(sp[1] for sp in pending) >= t_lo and span[2] <= t_hi:
                    out.extend(pending)
                    requests += 1
                pending = []
    return out, requests


class Cell:
    """One workload of ``BENCHMARK.json``, set up for one seed."""

    def __init__(self, bench: Bench, name: str, seed: int) -> None:
        self.bench = bench
        self.name = name
        self.seed = seed
        self.entry = bench.cell(name)
        self.cfg, self.mod = bench.config(self.entry["config"])
        self.traffic = bench.traffic(self.entry["traffic"])
        self.shared = self.mod.setup(self.cfg, self.traffic, seed)
        self.shared["seed"] = seed
        self._programs: Dict[Tuple, tuple] = {}

    def program(self, sizes: Dict[str, int]):
        key = tuple(sorted(sizes.items()))
        if key not in self._programs:
            self._programs[key] = self.mod.program(self.cfg, sizes)
        return self._programs[key]

    def written(self, sizes) -> Tuple[str, ...]:
        prog, _ = self.program(sizes)
        return tuple(sorted({s.write.array for s in prog.statements}))

    def store(self, sizes, client, index, prev=None):
        return self.mod.request_store(self.cfg, self.traffic, self.shared,
                                      sizes, client, index, prev)

    def reference(self, req: Request, dtype=np.float64) -> Dict[str, np.ndarray]:
        return self.mod.reference(self.cfg, self.shared, req.sizes,
                                  check.dense_inputs(req.store), dtype)


@dataclasses.dataclass
class Load:
    """A driver's load, started: the window's bounds on ``perf_counter``,
    and ``join()``, which waits for the driver's requests to end and returns
    what the driver reports on the run line."""

    start: float
    end: float
    join: Callable[[], dict]


class Log:
    """Every request a driver sent, and the sample of replies the check
    compares; drivers call ``add`` from any thread."""

    def __init__(self, seed: int) -> None:
        self.done: List[Request] = []
        self.kept = Sample(CHECK_SAMPLE, seed)
        self._lock = threading.Lock()

    def add(self, req: Request) -> None:
        with self._lock:
            self.done.append(req)
            if req.error is None:
                self.kept.offer(req)


def send(cell: Cell, svc, client: int, index: int, sizes: Dict[str, int],
         store: dict) -> Request:
    """One request through ``PlanService.submit(..., run=True)``, waited
    for; a request that raises is kept with its error, and the run goes
    on."""

    prog, options = cell.program(sizes)
    req = Request(client, index, sizes, time.perf_counter(), store=store)
    try:
        res = svc.submit(prog, options, store=store, run=True).result()
    except Exception as e:  # counted as failed
        req.t1 = time.perf_counter()
        req.error = repr(e)
    else:
        req.t1 = time.perf_counter()
        req.out = {a: res.store[a] for a in cell.written(sizes)}
    return req


def run_cell(
    bench: Bench,
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    require_chip: bool = True,
    log=lambda **kw: None,
    control: Optional[type] = None,
) -> dict:
    """Set up, warm, serve the window, check; returns the result object
    (the contract's last line).  ``control``: a NumPy float type; the plain
    reference computed in it takes the program's place in the check (see
    ``control.py``)."""

    entry = bench.cell(name)
    device = device_info(entry["chips"], require_chip)
    peaks = bench.peaks(device["kind"]) if require_chip else {}
    units = pin_profile(bench.dir, device["kind"] if require_chip else None)

    from repro.compile.lowering import use_persistent_compile_cache
    from repro.obs import metrics
    from repro.obs import trace as obs_trace
    from repro.serve import PlanService, ServiceOptions

    cache_dir = use_persistent_compile_cache()
    cell = Cell(bench, name, seed)
    driver = bench.driver(cell.traffic)
    svc = PlanService(ServiceOptions(backend="xla",
                                     workers=driver.workers(cell.traffic)))
    try:
        cases = []
        for sizes, store in driver.warm(cell):
            prog, options = cell.program(sizes)
            res = svc.submit(prog, options, store=store, run=True).result()
            cases.append(dict(sizes=sizes,
                              **describe_case(res.executable, prog, store)))
        traces_before = metrics.counter("xla.traces").value

        profile_dir = bench.root / ".bench_trace"
        window_perf_ns = None

        sent = Log(seed)
        load = driver.serve(
            cell, svc, seconds, sent,
            on_start=(lambda: (obs_trace.clear(), obs_trace.enable()))
            if trace else None,
        )
        setup_s = load.start - t_process
        if trace and require_chip:
            import jax

            # the profiler covers the window's last PROFILE_SECONDS; the
            # device and the benchmark's own annotation only: the Python
            # tracer would slow the host-bound layers severalfold
            time.sleep(max(0.0, load.end - PROFILE_SECONDS
                           - time.perf_counter()))
            shutil.rmtree(profile_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(str(profile_dir),
                                     profiler_options=options)
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                window_perf_ns = time.perf_counter_ns()
                time.sleep(max(0.0, load.end - time.perf_counter()))
            jax.profiler.stop_trace()
        time.sleep(max(0.0, load.end - time.perf_counter()))
        obs_trace.disable()
        driver_line = load.join()
        traces_window = metrics.counter("xla.traces").value - traces_before
        memory_peak = _memory_peak(require_chip)
    finally:
        svc.close()

    t_lo, t_hi = load.start, load.end
    done = sent.done
    counted = [r for r in done
               if r.error is None and r.t0 >= t_lo and r.t1 <= t_hi]
    failed = [r for r in done if r.error is not None]

    checks = check_sample(cell, sent.kept.requests(), seed, control)
    correct = bool(
        not failed and checks["checked"]["value"] >= 1
        and checks["gap"]["value"] <= checks["gap"]["limit"]
    )

    run_line = {
        "cell": name, "seed": seed, "cases": cases,
        "requests_in_window": len(counted), "attempted": len(done),
        "failed": len(failed), "xla_traces_in_window": traces_window,
        **driver_line, "units": units, "compile_cache": cache_dir,
        "latencies_ms": [round(r.latency_ms, 3) for r in counted],
    }
    if failed:
        run_line["first_error"] = failed[0].error
    log(run=run_line)
    if traces_window:
        raise RuntimeError(
            f"{traces_window} trace(s) inside the measured window: set-up "
            "did not warm every shape the window uses"
        )
    if not counted:
        raise RuntimeError("no request completed inside the window")

    device_red = None
    host, span_requests = [], 0
    if trace:
        host, span_requests = _host_spans(t_lo, t_hi)
        if window_perf_ns is not None:
            device_red = _reduce_profile(profile_dir, window_perf_ns, host)
            shutil.rmtree(profile_dir, ignore_errors=True)
    window = Window(
        seconds=seconds,
        setup_s=setup_s,
        requests=counted,
        spans=host,
        span_requests=span_requests,
        device=device_red,
        nest_bytes=_mean_nest_bytes(cell, counted),
        peaks=peaks,
    )
    kind = "per_layer" if trace else "end_to_end"
    out_metrics = {}
    for m in bench.metrics(name, kind):
        value = bench.reader(m["name"])(window)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": len(done),
        "failed": len(failed),
        "metrics": out_metrics,
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    if device_red is not None:
        result["device"]["busy_s"] = device_red["busy_s"]
        result["device"]["window_s"] = device_red["window_s"]
        result["breakdown"] = {
            "device_ops": device_red["device_ops"],
            "idle_gaps": device_red["idle_gaps"],
        }
    result["checks"] = checks
    return result


def _memory_peak(require_chip: bool) -> int:
    import jax

    if not require_chip:
        return 0
    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices()[:1]
    )


def _reduce_profile(profile_dir: Path, window_perf_ns: int, host) -> Optional[dict]:
    paths = sorted(profile_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        return None
    events = devtrace.load(str(paths[-1]))
    lo, _ = devtrace.window(events)
    # host spans to the trace's clock: the window annotation was opened at
    # perf_counter_ns() == window_perf_ns
    shift = lo - window_perf_ns
    spans = [(n, a * 1e9 + shift, b * 1e9 + shift, d)
             for n, a, b, d, _ in host]
    return devtrace.reduce(events, LEVEL_LOOP_MODULE, spans)


def _mean_nest_bytes(cell: Cell, requests: List[Request]) -> float:
    """Mean bytes of the nests served: each size's bytes from a store drawn
    anew for it (the bytes depend on shapes and index arrays alone)."""

    by_size: Dict[Tuple, int] = {}
    total = 0
    for r in requests:
        key = tuple(sorted(r.sizes.items()))
        if key not in by_size:
            prog, _ = cell.program(r.sizes)
            store = cell.store(r.sizes, r.client, r.index)
            by_size[key] = nest.nest_bytes(prog, check.dense_inputs(store))
        total += by_size[key]
    return total / len(requests)


class Sample:
    """The replies the check compares: ``k`` of the finished requests drawn
    from the seed (reservoir sampling in the order they finish), and the
    largest by its sizes.  Every other request lets go of its stores as it
    finishes, so the run holds no more than a few replies."""

    def __init__(self, k: int, seed: int) -> None:
        self.k = k
        self.rng = gen.rng(seed, 0xC4EC)
        self.kept: List[Request] = []
        self.largest: Optional[Request] = None
        self.seen = 0

    def offer(self, req: Request) -> None:
        dropped = [req]
        if self.seen < self.k:
            self.kept.append(req)
            dropped = []
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                dropped = [self.kept[j]]
                self.kept[j] = req
        self.seen += 1
        size = sum(req.sizes.values())
        if self.largest is None or size > sum(self.largest.sizes.values()):
            dropped.append(self.largest)
            self.largest = req
        for r in dropped:
            if r is not None and r is not self.largest and r not in self.kept:
                r.store = r.out = None

    def requests(self) -> List[Request]:
        out = sorted(self.kept, key=lambda r: r.index)
        if self.largest is not None and self.largest not in out:
            out.append(self.largest)
        return out


def check_sample(cell: Cell, picked: List[Request], seed: int,
                 control: Optional[type] = None) -> dict:
    """Each sampled reply against the float64 reference.  With ``control``
    (a NumPy float type), the reference computed in that type takes the
    program's place: its stores are what ``gap`` compares, on the same
    inputs, and the program's own widest gap is kept as ``program_gap``."""

    limit = cell.cfg["check"]["limit"]
    worst, worst_program = 0.0, 0.0
    for req in picked:
        want = cell.reference(req)
        got = req.out
        line = f"check {cell.name} seed={seed} request={req.index} sizes={req.sizes}"
        if control is not None:
            p = check.gap(req.out, want)
            worst_program = max(worst_program, p)
            line += f" program_gap={p!r}"
            got = {a: _cells(v) for a, v in cell.reference(req, control).items()}
        g = check.gap(got, want)
        worst = max(worst, g)
        print(f"{line} gap={g!r} limit={limit!r}", file=sys.stderr, flush=True)
    out = {"checked": {"value": len(picked), "limit": 1}}
    if control is not None:
        out["program_gap"] = {"value": worst_program, "limit": limit}
    out["gap"] = {"value": worst, "limit": limit}
    return out


def _cells(arr: np.ndarray) -> dict:
    idx = np.indices(arr.shape).reshape(arr.ndim, -1).T
    return dict(zip(map(tuple, idx.tolist()), arr.ravel().tolist()))
