#!/usr/bin/env python3
"""Smoke run of the served path on a TPU.

    python3 chip_smoke.py [--seed N]             # one chip
    python3 chip_smoke.py --chips 4 [--seed N]   # four chips

One chip: a ``PlanService(ServiceOptions(backend="xla", warm_profile=True))``
measures its cost profile on the chip, then serves each program of
:data:`repro.workloads.SMOKE_PROGRAMS` twice with ``run=True``
(``PlanService`` → ``plan()`` → ``compile("xla")`` → ``Executable.run()``).
The first request is cold; the second must be an artifact hit that traces
nothing.  Every result is held to the chip's contract against
``run_sequential`` on the same store (see ``repro.compile.lowering``).

Four chips: the same programs through ``compile("xla_spmd")`` on a
four-device mesh, each compared with the one-chip ``xla`` store and with
``run_sequential``; no other phase runs.

Everything runs in this one process.  Each earlier line of standard output
is one JSON record; the last is ``{"ok": true, "device": {...}}``.  Any
failure exits non-zero, and so does a run where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def compare(got: dict, want: dict) -> dict:
    """Bit-equality of two stores; else the largest difference in ulps of
    float64, relative to the cell's reference value, and normwise (relative
    to the largest reference magnitude of the array), which the chip's
    contract bounds."""

    import numpy as np

    if got.keys() != want.keys() or any(
        got[a].keys() != want[a].keys() for a in want
    ):
        raise AssertionError("stores cover different cells")

    def ordered(x):  # float64 bits as integers that count ulps
        i = x.view(np.int64)
        return np.where(i < 0, np.int64(-(2**63)) - i, i)

    out = {"bit_equal": True, "finite": True, "max_ulps": 0, "max_rel": 0.0,
           "max_normwise": 0.0, "cells_differing": 0, "cells": 0}
    for a in want:
        g = np.fromiter(got[a].values(), float)
        w = np.fromiter((want[a][k] for k in got[a]), float)
        err = np.abs(g - w)
        out["bit_equal"] &= bool((g == w).all())
        out["finite"] &= bool(np.isfinite(g).all() and np.isfinite(w).all())
        out["max_ulps"] = max(
            out["max_ulps"], int(np.abs(ordered(g) - ordered(w)).max())
        )
        out["max_rel"] = max(out["max_rel"], float(
            (err / np.maximum(np.abs(w), np.finfo(float).tiny)).max()
        ))
        out["max_normwise"] = max(out["max_normwise"], float(
            err.max() / max(np.abs(w).max(), np.finfo(float).tiny)
        ))
        out["cells_differing"] += int((g != w).sum())
        out["cells"] += int(g.size)
    return out


def check_contract(diff: dict, what: str) -> None:
    from repro.compile.lowering import TPU_F64_RTOL

    if not diff["finite"]:
        raise AssertionError(f"{what}: a non-finite value")
    if not diff["bit_equal"] and not diff["max_normwise"] <= TPU_F64_RTOL:
        raise AssertionError(
            f"{what}: normwise difference {diff['max_normwise']!r} exceeds "
            f"TPU_F64_RTOL={TPU_F64_RTOL!r}"
        )


def serve_twice(svc, name: str, prog, options, store: dict, want: dict):
    """Two ``run=True`` requests of one program; returns the warm store."""

    from repro.core.wavefront import _DenseStore
    from repro.obs import metrics

    out = None
    for request in ("cold", "warm"):
        before = metrics.snapshot()
        t0 = time.perf_counter()
        # the result store is a host dict: the request ends with a host
        # read of the device result
        res = svc.submit(prog, options, store=store, run=True).result()
        wall = time.perf_counter() - t0
        after = metrics.snapshot()
        delta = {  # every counter the request moved (gauges are floats)
            k: v - before.get(k, 0)
            for k, v in after.items()
            if type(v) is int and v != before.get(k, 0)
        }
        case, _ = res.executable.compiled.prepare(prog, _DenseStore(store))
        sched = case.schedule
        strategies = (
            [r.strategy for r in sched.scc.recurrences] if sched.scc else []
        )
        diff = compare(res.store, want)
        emit(
            program=name,
            backend=svc.options.backend,
            request=request,
            strategy=strategies or "layer",
            levels=case.n_levels,
            instances=sched.instances,
            wall_s=wall,
            counters=delta,
            **diff,
        )
        check_contract(diff, f"{name} {request} vs run_sequential")
        if request == "warm":
            if delta.get("xla.traces", 0) != 0:
                raise AssertionError(f"{name}: the warm request re-traced")
            if delta.get("plan_cache.artifact_hits", 0) != 1:
                raise AssertionError(f"{name}: the warm request missed")
        out = res.store
    return out


def one_chip(seed: int) -> None:
    import repro.calibrate as calibrate
    from repro.core import run_sequential
    from repro.serve import PlanService, ServiceOptions
    from repro.workloads import SMOKE_PROGRAMS, seeded_store, smoke_program

    t0 = time.perf_counter()
    with PlanService(
        ServiceOptions(backend="xla", warm_profile=True)
    ) as svc:
        prof = calibrate.active_profile()
        emit(
            phase="cost_profile",
            source=prof.source,
            units=prof.units,
            wall_s=time.perf_counter() - t0,
        )
        if prof.source == "default":
            raise AssertionError("no cost profile was measured or loaded")
        for name in SMOKE_PROGRAMS:
            prog, options = smoke_program(name)
            store = seeded_store(prog, seed)
            t1 = time.perf_counter()
            want = run_sequential(prog, store)
            emit(program=name, phase="run_sequential",
                 wall_s=time.perf_counter() - t1)
            serve_twice(svc, name, prog, options, store, want)


def four_chips(seed: int) -> None:
    from repro.compile import spmd
    from repro.core import run_sequential
    from repro.obs import metrics
    from repro.serve import PlanService, ServiceOptions
    from repro.workloads import SMOKE_PROGRAMS, seeded_store, smoke_program

    mesh_devices = spmd._mesh(spmd.shard_count()).devices.ravel()
    emit(
        phase="mesh",
        shards=spmd.shard_count(),
        devices=[str(d) for d in mesh_devices],
    )
    if len({d.id for d in mesh_devices}) != 4:
        raise AssertionError("the xla_spmd mesh does not span four chips")
    with PlanService(ServiceOptions(backend="xla")) as xla, PlanService(
        ServiceOptions(backend="xla_spmd")
    ) as sharded:
        for name in SMOKE_PROGRAMS:
            prog, options = smoke_program(name)
            store = seeded_store(prog, seed)
            want = run_sequential(prog, store)
            one = serve_twice(xla, name, prog, options, store, want)
            before = metrics.counter("spmd.collectives").value
            four = serve_twice(sharded, name, prog, options, store, want)
            vs_one = compare(four, one)
            emit(program=name, phase="xla_spmd_vs_xla",
                 collectives=metrics.counter("spmd.collectives").value
                 - before, **vs_one)
            check_contract(vs_one, f"{name} xla_spmd vs xla")
    if metrics.counter("spmd.collectives").value == 0:
        raise AssertionError("xla_spmd ran no collective")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every store and index array (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the xla path on one chip (default); 4: "
                    "xla_spmd on a four-chip mesh against the one-chip store")
    args = ap.parse_args(argv)

    from repro.compile.lowering import use_persistent_compile_cache

    cache_dir = use_persistent_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found (JAX platform "
            f"{devices[0].platform!r})"
        )
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit(phase="device", seed=args.seed, compile_cache=cache_dir, **device)
    if args.chips == 1:
        one_chip(args.seed)
    else:
        four_chips(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
