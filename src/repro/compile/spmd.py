"""``repro.compile.spmd`` — the multi-device SPMD wavefront backend.

The fifth executor (``parallelize(..., backend="xla_spmd")``).  A wavefront
level is embarrassingly parallel across lanes — exactly the parallelism the
paper's optimized send/wait sets expose — so this backend shards each
statement's padded (group × lane) index tables across a jax mesh with
``shard_map``:

  * every jitted input (level tables, the flat padded store, coverage,
    flags) enters the mapped region **replicated** (``PartitionSpec()``);
    inside, each device slices its contiguous block of a row's lanes by
    ``lax.axis_index``, gathers/computes only those lanes, and an
    ``lax.all_gather(..., tiled=True)`` reassembles the full lane vector in
    original order before the (replicated) masked scatter;
  * recurrence bands keep the store as the loop carry — replicated, with one
    all-gather per chunk step, lanes within the chunk sharded — so hybrid
    schedules shard without any cross-device scatter;
  * the per-lane arithmetic is byte-for-byte the base lowering's laundered
    strict ops (:mod:`repro.compile.lowering`), and everything outside the
    sharded gather/compute runs full-width on replicated data, identically
    on every device — sharded executions therefore stay bit-equal to the
    sequential oracle, the contract ``tests/oracle.py`` checks differentially
    on the whole corpus.

The interesting half is the cost model: :func:`spmd_level_cost` divides the
padded lane work by the device count but charges a flat dispatch cost plus a
per-lane collective cost for the gather — so ``CostModelPolicy`` picks a
wide skewed wavefront when the lane savings beat the collective tax and
narrow single-device chunking when they don't, per SCC, with both scored
offers recorded in ``summary()["scc"]`` (diffable via SYNC_REPORTS).

Cache discipline: the backend owns :data:`SPMD_CACHE`, a separate
:class:`~repro.compile.cache.CompileCache` whose factory builds
:class:`SpmdCompiledProgram` — structural keys carry no backend tag, so the
isolation (xla and xla_spmd artifacts must never alias) lives in the cache
instance.  The shard count is part of the trace **bucket** (it rides in
:class:`_SpmdCaseStatic`, the jit static) and of the per-bounds case key,
never the structural key: re-planning the same structure on a different
mesh is a structural hit that only rebuilds tables and re-traces.

Degenerate single-device meshes take the base class's exact code path (no
``shard_map``, no collectives): the trace is literally the single-device
trace.

Import is lazy like ``repro.compile`` itself: registration costs no jax;
the mesh (built from the seed's :func:`repro.launch.mesh.make_debug_mesh`,
with :func:`repro.launch.sharding._pick` guarding lane divisibility) is
constructed on first sharded execution and cached per device count —
``obs.reset_all()`` clears those handles via :func:`reset_spmd_caches` so
tests that vary ``--xla_force_host_platform_device_count`` stay
order-independent.
"""

from __future__ import annotations

import contextvars
import dataclasses
from typing import Optional, Tuple

from repro.obs import metrics as _metrics
from repro.compile import _next_pow2
from repro.compile.cache import CompileCache
from repro.compile.lowering import CompiledProgram, _CaseStatic

__all__ = [
    "SPMD_CACHE",
    "SpmdCompiledProgram",
    "device_count",
    "force_device_count",
    "reset_spmd_caches",
    "shard_count",
    "spmd_level_cost",
]


# ---------------------------------------------------------------------- #
# Device plumbing.  Two views on purpose:
#   * device_count()  — what the COST MODEL assumes (forcible, so policy
#     tests can score an 8-device mesh from a single-device pytest run);
#   * shard_count()   — what EXECUTION actually shards over, never more
#     than the process's real devices (a forced count degrades safely to
#     an unsharded run, still bit-equal).
# Both are power-of-two floors: lane tables pad to powers of two, so a
# pow2 shard count always divides the padded width.
# ---------------------------------------------------------------------- #

_FORCED: Optional[int] = None
_ACTUAL: Optional[int] = None
_MESHES: dict = {}


def _pow2_floor(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n).bit_length() - 1)


def force_device_count(n: Optional[int]) -> None:
    """Testing seam: pin the cost model's device count (None restores the
    process's real device count)."""

    global _FORCED
    _FORCED = None if n is None else int(n)


def _actual_devices() -> int:
    global _ACTUAL
    if _ACTUAL is None:
        import jax

        _ACTUAL = _pow2_floor(jax.device_count())
    return _ACTUAL


def device_count() -> int:
    """The mesh width the collective-aware cost model charges against."""

    if _FORCED is not None:
        return _pow2_floor(_FORCED)
    return _actual_devices()


def shard_count() -> int:
    """The mesh width execution actually shards over (≤ real devices)."""

    return min(device_count(), _actual_devices())


def _mesh(n: int):
    """The cached (n, 1) debug mesh over axes ("data", "model")."""

    mesh = _MESHES.get(n)
    if mesh is None:
        from repro.launch.mesh import make_debug_mesh

        mesh = _MESHES[n] = make_debug_mesh(data=n, model=1)
    return mesh


def reset_spmd_caches() -> None:
    """Drop every process-cached mesh/device handle plus the backend's
    structural cache (the ``obs.reset_all()`` hook): the next use re-reads
    ``jax.device_count()``, so tests that vary
    ``--xla_force_host_platform_device_count`` across subprocesses stay
    order-independent."""

    global _FORCED, _ACTUAL
    _FORCED = None
    _ACTUAL = None
    _MESHES.clear()
    SPMD_CACHE.clear()


# ---------------------------------------------------------------------- #
# The collective-aware cost hook.  Same units as xla_level_cost (per-step
# padded-lane work): the lane term is divided across devices, and sharded
# steps add a flat collective dispatch plus a per-lane gather term.  At
# device_count()==1 this is exactly xla_level_cost — the degenerate mesh
# must not perturb single-device strategy selection.
# ---------------------------------------------------------------------- #

# Hand-set defaults for the collective terms, in lane units.  Like the
# constants in repro.compile these are only the profile-less fallback:
# spmd_level_cost resolves all four unit costs late through
# repro.calibrate.units(), so a warmed profile (or a monkeypatched
# constant — the old import-by-value of XLA_STEP_LANE_UNITS made patches
# invisible here) takes effect on the next auction.

# flat per-step cost of issuing the lane-gather collective, in lane units
SPMD_COLLECTIVE_UNITS = 4.0
# per-lane cost of moving one gathered lane between devices
SPMD_COLLECTIVE_LANE_UNITS = 0.125


def spmd_level_cost(plan, ctx) -> float:
    """Per-SCC cost of a strategy offer on the sharded level loop.

    ``depth × statements × (flat + lanes/devices [+ collective(lanes)])``:
    a wide skewed wavefront amortizes its padded lanes across the mesh but
    pays the all-gather per step, so it wins only when ``lanes/n`` savings
    beat the collective tax — narrow chunked schedules (lanes ≤ devices)
    keep losing to plain chunking, which is the divergence-per-SCC the
    ``spmd_wide_wavefront`` bench and ``tests/test_spmd.py`` pin.
    """

    from repro.calibrate import units as _units

    u = _units()
    n = device_count()
    width = plan.max_width if plan.max_width else max(1, round(plan.width))
    # sharded tables pad lanes up to the mesh width (see _pad_lanes)
    lanes = max(_next_pow2(max(1, int(width))), n if n > 1 else 1)
    per_step = u["xla_step"] + u["xla_lane"] * lanes / n
    if n > 1:
        per_step += (
            u["spmd_collective"] + u["spmd_collective_lane"] * lanes
        )
    return float(plan.depth) * len(ctx.statements) * per_step


# ---------------------------------------------------------------------- #
# The sharded artifact
# ---------------------------------------------------------------------- #

# set while tracing inside the shard_map region: (axis name, shard count).
# _lane_values consults it so the same group_step code shards when mapped
# and stays full-width in the degenerate path.
_SHARD_AXIS: contextvars.ContextVar = contextvars.ContextVar(
    "spmd_shard_axis", default=None
)


@dataclasses.dataclass(frozen=True)
class _SpmdCaseStatic(_CaseStatic):
    """Trace-shaping static plus the shard count: device count changes the
    traced computation (slice + all_gather per read-bearing statement), so
    it belongs in the jit static — and therefore the bucket — never in the
    structural key."""

    n_shards: int = 1


class SpmdCompiledProgram(CompiledProgram):
    """A :class:`CompiledProgram` whose lane gather/compute is sharded
    across a device mesh (see module docstring for the exact split)."""

    def _level_cost_hook(self):
        return spmd_level_cost

    def _pad_lanes(self, wp: int) -> int:
        # lane dims must divide the mesh's data axis; both are powers of
        # two, so padding up to the shard count suffices
        return max(wp, shard_count())

    def _use_cond(self, wp: int) -> bool:
        # never wrap sharded group steps in lax.cond: the all_gather inside
        # would make the branches' collective schedules diverge.  The
        # active bit folds into the lane mask instead (the narrow-statement
        # path of the base lowering), which is mask-equivalent.
        return False

    def _band_rungs(self, wpb: int) -> int:
        # no width ladder when sharded: the per-shard lane slice +
        # all_gather reassembly needs every statement at its full padded
        # width (lane counts must divide the mesh axis).  Returning 0 keeps
        # the band's dynamic vector cut-free, so the base executor derives
        # L == 0 from its shape and stays on the single-loop path.
        return 0

    def _make_static(self, stmts, segments) -> _SpmdCaseStatic:
        return _SpmdCaseStatic(
            stmts=stmts, segments=segments, n_shards=shard_count()
        )

    def _case_key_extra(self) -> Tuple:
        # re-meshing rebuilds tables (lane padding depends on the shard
        # count) without touching the structural level
        return (shard_count(),)

    def _lane_values(self, k, ss, store, ridx, width, opaque_zero):
        ax = _SHARD_AXIS.get()
        if ax is None or not ss.reads:
            # degenerate mesh, or a zero-read broadcast statement (cheaper
            # replicated than gathered)
            return super()._lane_values(
                k, ss, store, ridx, width, opaque_zero
            )
        axis, n = ax
        from jax import lax

        shard = width // n
        lo = lax.axis_index(axis) * shard
        ridx_loc = [
            lax.dynamic_slice_in_dim(ix, lo, shard) for ix in ridx
        ]
        reads = [store[a][ix] for a, ix in zip(ss.reads, ridx_loc)]
        vals = self._batched[k](reads, shard, opaque_zero)
        # tiled gather concatenates shards in device order — the contiguous
        # blocks sliced above — restoring the original lane order
        return lax.all_gather(vals, axis, tiled=True)

    def _exec(
        self, static, n_levels, seg_dyn, tables, store, coverage, bad,
        opaque_zero,
    ):
        n = getattr(static, "n_shards", 1)
        if n <= 1:
            # the degenerate mesh IS the single-device trace
            return super()._exec(
                static, n_levels, seg_dyn, tables, store, coverage, bad,
                opaque_zero,
            )
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.launch.sharding import _pick

        mesh = _mesh(n)
        if _pick(mesh, n, "data") is None:  # pragma: no cover - mesh guard
            raise AssertionError(
                f"mesh data axis does not divide shard count {n}"
            )

        def body(n_levels, seg_dyn, tables, store, coverage, bad,
                 opaque_zero):
            token = _SHARD_AXIS.set(("data", n))
            try:
                return CompiledProgram._exec(
                    self, static, n_levels, seg_dyn, tables, store,
                    coverage, bad, opaque_zero,
                )
            finally:
                _SHARD_AXIS.reset(token)

        # every input and output is replicated (P()); the only sharded
        # values live transiently between the per-device lane slice and the
        # all_gather inside _lane_values.  check_vma=False because jax
        # cannot prove the replication invariant through the gathers.
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(n_levels, seg_dyn, tables, store, coverage, bad, opaque_zero)

    def execute(self, case, dense):
        n = getattr(case.static, "n_shards", 1)
        _metrics.gauge("spmd.devices").set(n)
        if n > 1:
            # host-side collective accounting: one all_gather executes per
            # read-bearing statement per level (cond-less dispatch runs
            # every statement each level; band steps likewise execute one
            # row per level of the band)
            hist = _metrics.histogram("spmd.shard_width")
            gathers = 0
            for ss, t in zip(case.static.stmts, case.tables):
                if not ss.reads:
                    continue
                hist.observe(t["lanemask"].shape[1] // n)
                gathers += case.n_levels
            _metrics.counter("spmd.collectives").inc(gathers)
        return super().execute(case, dense)


# the backend-owned structural cache: same four-level hierarchy, separate
# namespace (metrics under spmd_compile_cache.*), sharded artifact factory
SPMD_CACHE = CompileCache(
    metrics_prefix="spmd_compile_cache", factory=SpmdCompiledProgram
)


# ---------------------------------------------------------------------- #
# Backend registration: plan(...).compile("xla_spmd") / parallelize(...,
# backend="xla_spmd").  Mirrors repro.compile's xla registration, routed
# through SPMD_CACHE.
# ---------------------------------------------------------------------- #

def _spmd_prepare(
    optimized,
    retained,
    *,
    chunk_limit=None,
    scc_policy=None,
    model="doall",
    processors=None,
    deps=None,
):
    compiled, hit = SPMD_CACHE.get_or_compile(
        optimized.program,
        tuple(retained),
        model=model,
        processors=processors,
        chunk_limit=chunk_limit,
        scc_policy=scc_policy,
        deps=deps,
    )
    return {"compiled": compiled, "compile_hit": hit}


def _spmd_differential(sync, *, store=None, stalls=None):
    from repro.compile.executor import run_xla

    return run_xla(sync, store=store, compare=False, cache=SPMD_CACHE).store


def _spmd_run(sync, artifacts, *, store=None, stalls=None):
    from repro.compile.executor import execute_compiled, run_xla

    compiled = artifacts.get("compiled")
    if compiled is None:  # prepared elsewhere: resolve through the cache
        return run_xla(
            sync, store=store, compare=False, cache=SPMD_CACHE
        ).store
    return execute_compiled(compiled, sync, store=store)


def _register() -> None:
    from repro.core.parallelizer import BackendSpec, register_backend

    register_backend(
        BackendSpec(
            name="xla_spmd",
            prepare=_spmd_prepare,
            accepts=(
                "chunk_limit", "scc_policy", "model", "processors", "deps",
            ),
            level_cost=spmd_level_cost,
            differential=_spmd_differential,
            run=_spmd_run,
            description=(
                "multi-device SPMD wavefront: lanes sharded across a jax "
                "mesh via shard_map, collective-aware strategy costing "
                "(repro.compile.spmd)"
            ),
        )
    )


_register()
