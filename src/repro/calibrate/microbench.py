"""Synthetic microbenchmarks behind :func:`repro.calibrate.measure`.

Each benchmark drives the *real* machinery it prices — the jitted band
step of :mod:`repro.compile.lowering` (with its laundered per-lane
arithmetic, masking and scatter), the sharded variant with its
``all_gather``, and the NumPy wavefront interpreter — instead of an
idealized gather/scatter kernel, because the auction constants only have
to be honest about *this* code on *this* host.  The driver program is a
1-D chain recurrence ``a[i] = f(a[i-d])`` whose carried distance ``d``
pins the chunk width: forced ``scc_policy="chunk"`` lowers it to one
uniform recurrence band of ``~n/d`` levels, each ``d`` lanes wide, so the
per-level cost at several pow2 widths gives a clean (flat, per-lane)
linear fit.

Measurement discipline: the compiled backends are timed on the *jitted
level loop alone* — device buffers are packed once outside the clock and
the jit callable is invoked directly — so the O(cells) host wrapper
(store copy, densify, transfer) never leaks into per-level estimates;
the flat python dispatch that remains is cancelled by differencing two
problem sizes at the same width (only the level count changes between
them).

Everything here is jax-heavy and imported lazily by the package front
door; all compiles go through *local* :class:`CompileCache` instances so
measurement never pollutes the process-global structural caches.  Every
timed sample ticks ``calibrate.measurements`` — the counter the
persistence tests (and the CI artifact) watch to prove a reused profile
re-measures nothing.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple

from repro.obs import metrics as _metrics

# noise floor for fitted units: timing jitter can drive a least-squares
# intercept (or a collective delta) slightly negative, which a cost model
# must never see
_MIN_UNIT_US = 1e-4


def _chain_program(n: int, dist: int):
    from repro.core import ArrayRef, LoopProgram, Statement

    return LoopProgram(
        statements=(
            Statement("S1", ArrayRef("a", 0), (ArrayRef("a", -dist),)),
        ),
        bounds=((dist, n),),
    )


def _sync_for(prog):
    from repro.core import analyze, insert_synchronization

    return insert_synchronization(prog, analyze(prog))


def _best_of(fn, repeats: int) -> float:
    """Best-of-N wall time in seconds; every sample is one measurement."""

    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
        _metrics.counter("calibrate.measurements").inc()
    return best


def _fit_line(points) -> Tuple[float, float]:
    """Least-squares ``y = intercept + slope * x`` over ≥ 2 points."""

    xs = [float(x) for x, _ in points]
    ys = [float(y) for _, y in points]
    mx = sum(xs) / len(xs)
    my = sum(ys) / len(ys)
    var = sum((x - mx) ** 2 for x in xs)
    slope = (
        sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var
        if var
        else 0.0
    )
    return my - slope * mx, slope


def _jit_band_seconds(cache, n: int, dist: int, repeats: int) -> Tuple[
    float, int
]:
    """Best-of wall time of the *jitted level loop alone* for one chain
    program, plus its level count.  One warm ``run_xla`` builds (and
    traces) the artifact; the timed calls then replay the jit callable on
    pre-packed device buffers."""

    import jax

    from repro.compile.executor import run_xla
    from repro.compile.lowering import x64
    from repro.core.wavefront import _DenseStore

    prog = _chain_program(n, dist)
    sync = _sync_for(prog)
    init = prog.initial_store(pad=dist)
    rep = run_xla(
        sync,
        cache=cache,
        scc_policy="chunk",
        compare=False,
        store=init,
    )
    compiled = rep.compiled
    dense = _DenseStore({a: dict(c) for a, c in init.items()})
    case, _ = compiled.prepare(prog, dense)
    with x64():
        args = compiled.device_args(case, dense)

        def call():
            jax.block_until_ready(compiled._jit(case.static, *args))

        call()  # warm this exact shape (same bucket — no re-trace)
        best = _best_of(call, repeats)
    return best, rep.stats.levels


def _per_level_us(sample, n: int, dist: int, repeats: int) -> float:
    """Per-level µs via the two-size difference trick: only the level
    count changes between ``n // 2`` and ``n``, so flat per-call overhead
    cancels.  ``sample(size) -> (seconds, levels)``."""

    t_small, l_small = sample(n // 2)
    t_big, l_big = sample(n)
    if l_big <= l_small:  # degenerate sizing; avoid a zero division
        return max((t_big / max(l_big, 1)) * 1e6, _MIN_UNIT_US)
    return max(
        ((t_big - t_small) / (l_big - l_small)) * 1e6, _MIN_UNIT_US
    )


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _pow2_floor(n: int) -> int:
    return 1 if n <= 1 else 1 << (n.bit_length() - 1)


def measure_units(
    *,
    n: int = 8192,
    widths: Tuple[int, ...] = (8, 64, 512),
    repeats: int = 3,
    spmd: bool = False,
) -> Tuple[Dict[str, float], dict]:
    """Run the suite; returns ``(units, meta)`` for a fresh CostProfile.

    ``widths`` must be powers of two (each is a carried distance = chunk
    width = padded lane count); ``n`` the largest chain length (the small
    size is ``n // 2``).  Everything runs on the default device unless
    ``spmd=True``, which also measures the collectives over every local
    device (on a one-device host that stays a no-op); otherwise the
    hand-set collective ratios are scaled by the measured per-lane cost so
    the profile stays on one unit scale.  A serving process that owns one
    chip of a multi-chip host therefore never touches the others.
    """

    from repro.compile.cache import CompileCache

    widths = tuple(sorted({_next_pow2(max(2, w)) for w in widths}))
    if len(widths) < 2:
        raise ValueError(
            f"need >= 2 distinct pow2 widths to fit a lane slope, got "
            f"{widths!r}"
        )
    if n // 2 <= 4 * max(widths):
        raise ValueError(
            f"n={n} too small for widths {widths!r}: the smallest run must "
            "still produce a multi-level recurrence band"
        )
    meta: dict = {"n": n, "widths": list(widths), "repeats": repeats}

    # -- xla band step: flat per-level cost + per padded lane ----------- #
    xla_cache = CompileCache()
    xla_points = [
        (
            w,
            _per_level_us(
                lambda size, w=w: _jit_band_seconds(
                    xla_cache, size, w, repeats
                ),
                n,
                w,
                repeats,
            ),
        )
        for w in widths
    ]
    step, lane_slope = _fit_line(xla_points)
    xla_lane = max(lane_slope, _MIN_UNIT_US)
    xla_step = max(step, _MIN_UNIT_US)
    meta["xla_per_level_us"] = {str(w): y for w, y in xla_points}

    # -- spmd band step: collective flat + per gathered lane ------------ #
    n_dev = 1
    if spmd:
        import jax

        n_dev = _pow2_floor(jax.local_device_count())
    if n_dev >= 2:
        from repro.compile.spmd import SpmdCompiledProgram

        spmd_cache = CompileCache(factory=SpmdCompiledProgram)
        deltas = []
        for w in widths:
            wp = max(w, n_dev)  # the sharded artifact's lane padding
            per_level = _per_level_us(
                lambda size, w=w: _jit_band_seconds(
                    spmd_cache, size, w, repeats
                ),
                n,
                w,
                repeats,
            )
            deltas.append(
                (wp, per_level - (xla_step + xla_lane * wp / n_dev))
            )
        coll, coll_slope = _fit_line(deltas)
        spmd_collective = max(coll, _MIN_UNIT_US)
        spmd_collective_lane = max(coll_slope, _MIN_UNIT_US)
        meta["spmd_delta_us"] = {str(w): d for w, d in deltas}
        meta["spmd_devices"] = n_dev
    else:
        # one device: keep the hand-set collective *ratios* (they are
        # expressed in lane units) on the measured lane scale
        import repro.compile.spmd as _spmd

        spmd_collective = _spmd.SPMD_COLLECTIVE_UNITS * xla_lane
        spmd_collective_lane = _spmd.SPMD_COLLECTIVE_LANE_UNITS * xla_lane
        meta["spmd_delta_us"] = "skipped (one device)"
        meta["spmd_devices"] = n_dev

    # -- interpreter dispatch: per batched group of the NumPy wavefront - #
    from repro.core.wavefront import run_wavefront

    def wf_sample(size):
        prog = _chain_program(size, widths[0])
        sync = _sync_for(prog)
        init = prog.initial_store(pad=widths[0])
        run_wavefront(  # warm analysis/schedule caches outside the clock
            sync, scc_policy="chunk", compare=False, store=init
        )
        secs = _best_of(
            lambda: run_wavefront(
                sync, scc_policy="chunk", compare=False, store=init
            ),
            repeats,
        )
        levels = run_wavefront(
            sync, scc_policy="chunk", compare=False, store=init
        ).stats.levels
        return secs, levels

    dispatch = max(
        _per_level_us(wf_sample, n, widths[0], repeats), _MIN_UNIT_US
    )
    meta["wavefront_per_group_us"] = dispatch

    units = {
        "xla_step": xla_step,
        "xla_lane": xla_lane,
        "spmd_collective": spmd_collective,
        "spmd_collective_lane": spmd_collective_lane,
        "dispatch": dispatch,
    }
    return units, meta
