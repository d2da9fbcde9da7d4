"""JAX/XLA lowering of a wavefront schedule to one jitted executable.

The NumPy wavefront backend (:mod:`repro.core.wavefront`) interprets the
level schedule: a Python loop over ~2·N levels, each doing a small gather /
compute / scatter.  For the deep, narrow schedules the paper's loops produce
(Alg. 6 at 1024 iterations has 2047 levels of width ≤ 2 after the batched
level 0) the Python-level dispatch dominates.  This module compiles the whole
level loop into a single XLA computation instead:

  * every array of the memory image becomes one flat ``float64`` buffer with
    a *trash cell* appended past the live data — masked-out lanes scatter
    there, so padding never corrupts the store;
  * each statement's wavefront groups are packed level-sorted into padded
    index tables of shape ``(G, W)`` (``G`` groups padded with a sentinel
    row, ``W`` lanes padded with redirected indices) — per-statement widths,
    so a 1024-wide DOALL statement does not inflate a width-1 chain;
  * the executable is a ``lax.fori_loop`` over levels whose body keeps one
    cursor per statement: when the cursor's next group belongs to the
    current level, a ``lax.cond`` runs that group's vectorized
    gather/compute/scatter and advances the cursor.  Per level, only the
    statements that actually have work pay for it.

Because the tables are *data*, the group/lane axes are padded to
power-of-two buckets, and every per-bounds scalar (level count, segment
extents, cursor bases, chunk counts) is a *traced argument*, one traced
artifact serves any iteration count whose bucketed shapes coincide.  That is
the third level of the cache hierarchy — structure → **bucket** → trace →
per-bounds tables: the structural cache (:mod:`repro.compile.cache`) maps a
dependence structure to one :class:`CompiledProgram`; inside it, jax's jit
cache keys each trace on the bounds-free statics plus bucketed shapes (the
"bucket", mirrored host-side in ``PreparedCase.bucket`` and counted through
the ``xla.traces`` / ``xla.bucket_*`` metrics); under each trace, the
per-(bounds, layout, content) table LRU supplies the values.  A serving loop
over a fixed structure-and-bucket mix therefore re-traces exactly zero times
at steady state, which ``benchmarks/run.py``'s ``serve_sustained_traffic``
row gates on.

Hybrid (SCC-condensed) schedules add one more structure: a cyclic SCC's
chunked DOACROSS block appears as a *recurrence band* — a run of consecutive
levels whose active groups are the same statements at consecutive table rows.
Those bands lower to a nested ``lax.fori_loop`` over chunks with the store
(the recurrence carry) in the loop state: no per-level ``lax.cond`` dispatch,
no cursor bookkeeping, only the band's statements in the loop body.  The
band detector is strategy-agnostic: a unimodular-*skew* SCC's diagonal
wavefronts and a per-SCC-*dswp* pipeline's lane progressions also advance
one table row per level in lockstep, so they collapse into the same nested
loop — the skew's index remap back to original coordinates is already folded
into the level tables (the schedule emits original iteration points), and
each dswp lane is simply its statement's own (group × lane) table.  Levels
outside any band keep the generic cursor machinery, so pipelined schedules
that interleave a recurrence with downstream acyclic levels still compile.
Only the segment *skeleton* (kinds + band statement sets) is static; segment
extents, cursor bases and chunk counts travel in per-segment ``int32``
vectors (``PreparedCase.seg_dyn``), so hybrid artifacts bucket-share traces
exactly like acyclic ones.  Schedules without recurrence SCCs take a single
level loop over a traced level count.

Everything runs in ``float64`` (inside :func:`x64`), so on XLA:CPU stores
are bit-equal to :func:`repro.core.ir.run_sequential` — the same contract
the other executors are held to by ``tests/oracle.py``.  A TPU has no
float64 hardware: XLA:TPU emulates it, and there the contract is
:data:`TPU_F64_RTOL` (see :func:`_protect`).

Error parity with the NumPy backend: an access outside the initialized store
raises ``KeyError("… outside the initialized store …")`` (statically for
unguarded statements, via an in-loop flag for guard-dependent ones), and a
read of an uninitialized cell of a sparse store raises
``KeyError("… uninitialized …")`` (tracked at run time with per-array
coverage buffers, since an earlier level may legitimately initialize a cell a
later level reads).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.core.dependence import Dependence
from repro.core.ir import LoopProgram, is_indirect
from repro.core.policy import SccPolicyLike
from repro.core.wavefront import (
    WavefrontSchedule,
    WavefrontStats,
    _DenseStore,
    schedule_levels,
)


# every span of the program also opens a profiler annotation, so a profiler
# capture holds the spans on the device trace's clock
_trace.set_annotation(TraceAnnotation)


class XlaLoweringError(ValueError):
    """The program cannot be lowered to XLA (e.g. untraceable compute fn)."""


# one rounding convention for table padding AND the cost model's padded-lane
# estimate (repro.compile.xla_level_cost) — they must never drift apart
from repro.compile import _next_pow2  # noqa: E402


# Width ladder for recurrence bands (ROADMAP 3b).  A band's ramp-up and
# ramp-down levels run at sliced lane widths — halvings of the padded band
# width, at most WIDTH_LADDER_RUNGS of them, never narrower than
# WIDTH_LADDER_MIN lanes (below that the per-step dispatch cost dwarfs any
# lane saving).  Read late (module attribute lookup, not captured values)
# so benchmarks can pin ``lowering.WIDTH_LADDER_RUNGS = 0`` for an unsplit
# control build.
WIDTH_LADDER_RUNGS = 3
WIDTH_LADDER_MIN = 8


# The chip's contract.  XLA:TPU emulates float64 with pairs of float32: on a
# v5e a host→device→host round trip moves values by up to 8 ulps, a single
# mul/div is off by up to 2^-44 relative to its operands, and magnitudes
# beyond float32's range (~3.4e38) overflow.  So on a TPU a store is held
# to ``run_sequential`` normwise per array, max|got - want| ≤ TPU_F64_RTOL ·
# max|want|, with 2^-32 = 2^-44 per operation over dependence chains of up
# to 2^12 operations.  XLA:CPU stays bit-equal.
TPU_F64_RTOL = 2.0**-32


def x64():
    """The float64 scope every level-loop execution runs in (a context
    manager)."""

    import jax

    return jax.enable_x64(True)


def use_persistent_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and
    nothing is set in code; otherwise ``<checkout>/.jax_cache``.  The path
    is fixed, never built from a temporary name, a process id or the time,
    so a later process on the same checkout finds what an earlier one
    compiled."""

    import os
    from pathlib import Path

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------- #
# Strict lane arithmetic.  XLA's CPU emitter compiles the whole computation
# into one LLVM function with aggressive FP op fusion, so a multiply feeding
# an add is contracted into an FMA — a 1-ulp divergence from the scalar
# interpreters that appears and disappears with fusion context, and that
# neither ``lax.optimization_barrier`` nor the documented fast-math flags
# suppress (the contraction happens below HLO, in instruction selection).
#
# The compute functions are therefore evaluated on proxies that *launder*
# every arithmetic result through an integer ``xor`` with a runtime-opaque
# zero (a scalar argument of the jitted executable, so neither XLA's
# algebraic simplifier nor LLVM's InstCombine can fold it away).  The
# laundering is bit-exact — including -0.0 and NaN — and severs every
# producer→consumer float pattern, forcing each IEEE op to round
# individually exactly like the sequential oracle.  Cost: two bitcasts and
# an integer xor per op per lane, on expressions a handful of ops long.
# Only an XLA:CPU executable carries it (see ``_protect``).
# ---------------------------------------------------------------------- #

class _StrictLane:
    """Operator-intercepting wrapper around a lane vector.

    ``z`` is the runtime-opaque int64 zero used to launder results.
    """

    __slots__ = ("x", "z")

    def __init__(self, x, z) -> None:
        self.x = x
        self.z = z

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"_StrictLane({self.x!r})"

    def __bool__(self) -> bool:
        # `if lane:` would silently take one branch for every lane; raising
        # routes value-branching computes into the vmap fallback, where jax
        # gives the same treatment (trace error → XlaLoweringError)
        raise TypeError(
            "compute fn branches on a lane vector's truth value; "
            "per-lane branching is not vectorizable — use arithmetic "
            "selects or run backend='wavefront'"
        )


def _unwrap(v):
    return v.x if isinstance(v, _StrictLane) else v


def _launder(x, z):
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(x, jnp.int64)
    return lax.bitcast_convert_type(jnp.bitwise_xor(bits, z), jnp.float64)


def _protect(x, z):
    """Launder ``x`` on XLA:CPU; pass it through everywhere else.

    The form is chosen per lowering platform (``lax.platform_dependent``
    keeps only the branch of the platform the executable is compiled for).
    XLA:TPU emulates float64 and its rewrite has no rule for a 64-bit
    bitcast, so the TPU executable carries no laundering."""

    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(x)
    if x.dtype != jnp.float64:  # int/bool intermediates are already exact
        return x
    return lax.platform_dependent(
        x, z, cpu=_launder, default=lambda x, z: x
    )


def _launder_operand(v, z):
    """Make an operand runtime-opaque (python scalars become laundered f64
    constants).  Used for division-family ops: XLA rewrites division by a
    *compile-time* constant into a reciprocal multiply, which is not
    correctly rounded (e.g. ``x / 3`` differs from IEEE by 1 ulp for some
    x); a laundered divisor forces a true hardware ``fdiv``."""

    import jax.numpy as jnp

    if isinstance(v, (int, float)):
        v = jnp.asarray(float(v), jnp.float64)
    return _protect(v, z)


def _strict_binop(op, swap: bool, launder_operands: bool = False):
    def method(self, other):
        a, b = _unwrap(self), _unwrap(other)
        if launder_operands:
            a, b = _launder_operand(a, self.z), _launder_operand(b, self.z)
        if swap:
            a, b = b, a
        return _StrictLane(_protect(op(a, b), self.z), self.z)

    return method


def _strict_unop(op):
    def method(self):
        return _StrictLane(_protect(op(self.x), self.z), self.z)

    return method


def _install_strict_ops() -> None:
    import operator

    for name, op, launder in [
        ("add", operator.add, False),
        ("sub", operator.sub, False),
        ("mul", operator.mul, False),
        ("truediv", operator.truediv, True),
        ("floordiv", operator.floordiv, True),
        ("mod", operator.mod, True),
        ("pow", operator.pow, True),
    ]:
        setattr(
            _StrictLane, f"__{name}__", _strict_binop(op, False, launder)
        )
        setattr(
            _StrictLane, f"__r{name}__", _strict_binop(op, True, launder)
        )
    for name, op in [
        ("neg", operator.neg),
        ("pos", operator.pos),
        ("abs", operator.abs),
    ]:
        setattr(_StrictLane, f"__{name}__", _strict_unop(op))
    for name, op in [
        ("lt", operator.lt),
        ("le", operator.le),
        ("gt", operator.gt),
        ("ge", operator.ge),
        ("eq", operator.eq),  # value comparison, NOT python identity —
        ("ne", operator.ne),  # default object.__eq__ would be silently wrong
    ]:
        # comparisons exit the strict domain (no rounding to protect)
        setattr(
            _StrictLane,
            f"__{name}__",
            lambda self, other, op=op: op(_unwrap(self), _unwrap(other)),
        )


_STRICT_READY = False


def _ensure_strict_ops() -> None:
    global _STRICT_READY
    if not _STRICT_READY:
        _install_strict_ops()
        _STRICT_READY = True


# ---------------------------------------------------------------------- #
# Trace-shaping statics: everything (beyond argument shapes) that changes
# the structure of the traced computation.  Hashable by value, so prepared
# cases with identical statics and bucketed shapes share one jit trace.
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class _StmtStatic:
    name: str
    write: str
    reads: Tuple[str, ...]
    guard: Optional[str]
    has_oob: bool                  # tables carry an "oob" lane mask to flag
    cov_reads: Tuple[bool, ...]    # per read: consult the coverage buffer
    cov_guard: bool
    cov_write: bool                # scatter updates the coverage buffer
    # narrow statements run every level with the active bit folded into the
    # lane mask (a handful of trash-redirected lanes) — cheaper than a
    # lax.cond, whose pass-through copies the write array at every level;
    # wide statements keep the cond so inactive levels don't pay their lanes
    use_cond: bool = True


@dataclasses.dataclass(frozen=True)
class _CaseStatic:
    stmts: Tuple[_StmtStatic, ...]
    # segmented level loop (hybrid schedules with recurrence SCCs only) as a
    # bounds-free *skeleton*:
    #   ("wave",)            — generic dispatcher segment
    #   ("rec", (k1, ...))   — nested fori_loop band running statements k1…
    # Every per-bounds scalar (segment extents, cursor bases, chunk counts,
    # band row bases) rides in ``PreparedCase.seg_dyn`` as a *traced* jit
    # argument instead, so two bounds whose skeleton and bucketed shapes
    # coincide share one trace — the "bucket" level of the cache hierarchy
    # (structure → bucket → trace → per-bounds tables).
    # None → the single traced-bound level loop (likewise shared across
    # bounds with equal bucketed shapes)
    segments: Optional[Tuple[Tuple, ...]] = None


@dataclasses.dataclass
class PreparedCase:
    """Per-(bounds, store layout) lowering artifacts: level tables + layout."""

    static: _CaseStatic
    n_levels: int
    tables: Tuple[Dict[str, np.ndarray], ...]   # per statement
    arrays: Tuple[str, ...]
    origin: Dict[str, Tuple[int, ...]]
    shapes: Dict[str, Tuple[int, ...]]
    flat_sizes: Dict[str, int]                  # live cells per array
    padded_sizes: Dict[str, int]                # flat buffer length (≥ live+1)
    sparse: Tuple[str, ...]                     # arrays carrying coverage
    # arrays some statement writes: the only ones a run can change, so the
    # only ones copied back (never part of the trace identity)
    written: Tuple[str, ...]
    schedule: WavefrontSchedule
    # per-segment dynamic scalars (see _CaseStatic.segments):
    #   wave → [lo, hi, cursors0…] ; rec → [n_chunks, row0…]
    seg_dyn: Tuple[np.ndarray, ...] = ()
    bucket: Tuple = ()                          # trace-identity key (host view)
    # lanes a run computes with the mask set, and lanes it computes in all
    # (each statement's groups × its padded width): the xla.lanes_* counters
    lanes_live: int = 0
    lanes_padded: int = 0
    _device_tables: Optional[Tuple] = None      # jnp copies, converted once
    _device_segdyn: Optional[Tuple] = None


_OOB_MSG = (
    "access outside the initialized store — widen the pad of initial_store()"
)
_HOLE_MSG = (
    "read of an uninitialized cell — the provided store does not cover "
    "this access"
)


class CompiledProgram:
    """One structural cache entry: a lowering plan plus its jit executable.

    Built once per (statement graph, retained dependences, execution model);
    per-bounds level tables and per-shape XLA specializations are nested
    caches inside.  ``parallelize(..., backend="xla")`` attaches the handle
    to the :class:`~repro.core.parallelizer.ParallelizationReport`.
    """

    # prepared-case LRU bound: a long-running server whose bounds vary per
    # request must not accumulate level tables without limit
    MAX_CASES = 32

    def __init__(
        self,
        key: str,
        program: LoopProgram,
        retained: Sequence[Dependence],
        model: str = "doall",
        processors: Optional[Dict[str, object]] = None,
        chunk_limit: Optional[int] = None,
        scc_policy: SccPolicyLike = None,
        deps: Optional[str] = None,
    ) -> None:
        import collections
        import threading

        import jax

        self.key = key
        self.program = program
        self.retained = tuple(retained)
        self.model = model
        self.processors = dict(processors) if processors else None
        self.chunk_limit = chunk_limit
        self.scc_policy = scc_policy
        # non-affine dependence mode: None (conservative proxies),
        # "inspect" (exact per-bounds instance graph), or "speculate"
        # (optimistic schedule; validation + rollback live in the run
        # wrapper — repro.compile.executor.execute_compiled)
        self.deps_mode = deps
        self.cache = None  # back-reference set by the owning CompileCache
        self._cases: "collections.OrderedDict[Tuple, PreparedCase]" = (
            collections.OrderedDict()
        )
        self._lock = threading.Lock()
        self._batched = [
            self._make_batched(s) for s in program.statements
        ]
        # trace accounting (the "bucket" cache level): _buckets collects the
        # distinct trace identities served so far; _trace_count is bumped by
        # the Python body of _exec, which jax runs exactly once per trace —
        # at steady state the two agree, and the service/bench judge
        # re-trace rate on the registry counter behind them
        self._buckets: set = set()
        self._trace_count = 0
        self._jit = jax.jit(self._exec, static_argnums=(0,))

    # ------------------------------------------------------------------ #
    @property
    def prepared_cases(self) -> int:
        return len(self._cases)

    @property
    def trace_count(self) -> int:
        """Times jax traced the executable (Python body executions)."""

        return self._trace_count

    @property
    def bucket_count(self) -> int:
        """Distinct (skeleton, bucketed shapes) trace identities served."""

        with self._lock:
            return len(self._buckets)

    def cache_stats(self) -> Dict[str, int]:
        if self.cache is None:  # pragma: no cover - standalone use
            return {}
        return self.cache.stats.as_dict()

    # ------------------------------------------------------------------ #
    # Backend-specialization hooks.  The sharded artifact
    # (repro.compile.spmd.SpmdCompiledProgram) overrides these; the base
    # definitions pin the single-device behavior exactly as before.
    # ------------------------------------------------------------------ #

    def _level_cost_hook(self):
        """Per-level step-cost model handed to the scheduling policy."""

        from repro.compile import xla_level_cost

        return xla_level_cost

    def _pad_lanes(self, wp: int) -> int:
        """Final lane padding (``wp`` is already a power of two)."""

        return wp

    def _use_cond(self, wp: int) -> bool:
        """Whether a statement of padded width ``wp`` gets a lax.cond (wide)
        or runs condless with the active bit folded into the lane mask."""

        return wp > 32

    def _make_static(self, stmts, segments) -> _CaseStatic:
        """Build the trace-shaping static for a prepared case."""

        return _CaseStatic(stmts=stmts, segments=segments)

    def _case_key_extra(self) -> Tuple:
        """Extra components appended to the per-bounds case key (the sharded
        artifact adds the shard count so re-meshing rebuilds tables without
        touching the structural level)."""

        return ()

    def _lane_values(self, k, ss, store, ridx, width, opaque_zero):
        """Gather + vectorized compute of one table row's lanes (the part of
        a group step the sharded artifact splits across devices)."""

        reads = [store[a][ix] for a, ix in zip(ss.reads, ridx)]
        return self._batched[k](reads, width, opaque_zero)

    # ------------------------------------------------------------------ #
    @staticmethod
    def _make_batched(stmt):
        """Vectorized compute over whole lane vectors.

        Reads are wrapped in :class:`_StrictLane` so every arithmetic op
        rounds individually (bit-identical to the scalar interpreters);
        compute functions that don't speak the proxy protocol (e.g. calling
        ``jnp.*`` directly) fall back to a plain ``jax.vmap`` — traceable
        but subject to XLA's usual elementwise codegen."""

        import jax
        import jax.numpy as jnp

        _ensure_strict_ops()
        n_reads = len(stmt.reads)

        def batched(reads: List, width: int, opaque_zero):
            if n_reads == 0:
                return jnp.broadcast_to(
                    jnp.asarray(stmt.compute(), jnp.float64), (width,)
                )
            try:
                out = jnp.asarray(
                    _unwrap(
                        stmt.compute(
                            *(_StrictLane(r, opaque_zero) for r in reads)
                        )
                    ),
                    jnp.float64,
                )
                if out.shape == (width,):
                    return out
                if out.ndim == 0:
                    return jnp.broadcast_to(out, (width,))
            except TypeError:
                # the compute fn does not speak the proxy protocol (it
                # branched on a lane, or handed a proxy to jnp directly)
                pass
            try:
                return jnp.asarray(jax.vmap(stmt.compute)(*reads), jnp.float64)
            except Exception as e:
                raise XlaLoweringError(
                    f"compute function of {stmt.name!r} is not traceable by "
                    f"jax ({e!r}); run this program with backend='wavefront' "
                    "or make the compute fn jnp-compatible"
                ) from e

        return batched

    # ------------------------------------------------------------------ #
    # Table construction (host side, NumPy)
    # ------------------------------------------------------------------ #

    @staticmethod
    def _layout_key(dense: _DenseStore) -> Tuple:
        return tuple(
            sorted(
                (a, dense.origin[a], dense.data[a].shape, a in dense.mask)
                for a in dense.data
            )
        )

    def _content_arrays(self, program: LoopProgram) -> Tuple[str, ...]:
        """The arrays whose contents the level tables depend on: the index
        arrays, and under ``deps="inspect"`` the read-only guard arrays the
        inspector resolves."""

        if self.deps_mode == "inspect":
            from repro.core.inspector import content_arrays

            return content_arrays(program)
        return program.index_arrays()

    def _content_key(
        self, program: LoopProgram, dense: _DenseStore
    ) -> Optional[str]:
        """Content digest of :meth:`_content_arrays` for indirect programs.

        The level tables of an indirect access are computed from the index
        array's *values* (and, under ``deps="inspect"``, so is the schedule
        itself, with the live set its guards leave), so the per-bounds case
        key must cover them — this is where store-dependent state lives,
        never in the bounds-free structural key.  Affine programs return
        None and pay nothing.
        """

        if not program.has_indirect():
            return None
        h = hashlib.sha1()
        for arr in sorted(self._content_arrays(program)):
            h.update(arr.encode())
            h.update(repr(dense.origin[arr]).encode())
            h.update(dense.data[arr].tobytes())
            covered = dense.mask.get(arr)
            if covered is not None:
                h.update(covered.tobytes())
        return h.hexdigest()

    def _index_store(self, program: LoopProgram, dense: _DenseStore) -> dict:
        """Dict-form view of just the content arrays (inspector input)."""

        return dense.to_dicts(self._content_arrays(program))

    def prepare(
        self, program: LoopProgram, dense: _DenseStore
    ) -> Tuple[PreparedCase, bool]:
        """Level tables for these bounds + this store layout (memoized in a
        bounded LRU; thread-safe for concurrent serving)."""

        with _trace.span("compile.tables_lookup"):
            key = (
                program.bounds,
                self._layout_key(dense),
                self._content_key(program, dense),
                *self._case_key_extra(),
            )
            with self._lock:
                case = self._cases.get(key)
                if case is not None:
                    self._cases.move_to_end(key)
        if case is not None:
            return case, True
        with _trace.span("compile.tables", bounds=str(program.bounds)):
            built = self._build_case(program, dense)
        with self._lock:
            case = self._cases.get(key)  # lost a build race: reuse theirs
            if case is None:
                self._cases[key] = case = built
                while len(self._cases) > self.MAX_CASES:
                    self._cases.popitem(last=False)
        return case, False

    def _build_case(
        self, program: LoopProgram, dense: _DenseStore
    ) -> PreparedCase:
        missing = [a for a in program.arrays() if a not in dense.data]
        if missing:
            raise KeyError(
                f"store is missing arrays {missing} referenced by the program"
            )
        # schedule under the compiled backend's own step-cost model: the
        # default scheduling policy scores strategies through the artifact's
        # level-cost hook (xla_level_cost here, the collective-aware
        # spmd_level_cost in the sharded subclass), so the same "auto" knob
        # can resolve to chunk here while the NumPy interpreter resolves it
        # to skew (forced strategies and explicit policy instances are
        # untouched by the hook)
        level_cost = self._level_cost_hook()

        retained = list(self.retained)
        instance_edges, inactive = None, frozenset()
        if self.deps_mode is not None and program.has_indirect():
            from repro.core.inspector import (
                affine_retained,
                inspect_dependences,
            )

            # drop the conservative non-affine proxies; under "inspect" the
            # exact per-bounds instance graph replaces them, under
            # "speculate" nothing does (optimistic doall — the run wrapper
            # validates post-hoc and rolls back to the deps=None artifact)
            retained = list(affine_retained(retained))
            if self.deps_mode == "inspect":
                inspection = inspect_dependences(
                    program, self._index_store(program, dense)
                )
                instance_edges = inspection.edges
                inactive = inspection.inactive
        sched = schedule_levels(
            program,
            retained,
            model=self.model,
            processors=self.processors,
            chunk_limit=self.chunk_limit,
            scc_policy=self.scc_policy,
            level_cost=level_cost,
            instance_edges=instance_edges,
            inactive=inactive,
        )
        n_levels = sched.depth
        arrays = tuple(sorted(dense.data))
        origin = {a: dense.origin[a] for a in arrays}
        shapes = {a: dense.data[a].shape for a in arrays}
        flat_sizes = {a: int(np.prod(shapes[a])) for a in arrays}
        padded_sizes = {a: _next_pow2(flat_sizes[a] + 1) for a in arrays}
        sparse = tuple(a for a in arrays if a in dense.mask)
        targets = {s.write.array for s in program.statements}
        written = tuple(a for a in arrays if a in targets)

        per_stmt: Dict[str, List[Tuple[int, np.ndarray]]] = {}
        for lvl, groups in enumerate(sched.levels):
            for g in groups:
                per_stmt.setdefault(g.statement, []).append(
                    (lvl, np.asarray(g.iterations, dtype=np.int64))
                )

        stmt_statics: List[_StmtStatic] = []
        tables: List[Dict[str, np.ndarray]] = []
        # Actual (unpadded) lane count of every table row, in row order, and
        # each statement's padded width — the width ladder's raw material.
        row_widths: List[List[int]] = []
        wps: List[int] = []
        for s in program.statements:
            entries = per_stmt.get(s.name, [])
            G = len(entries)
            W = max((pts.shape[0] for _, pts in entries), default=1)
            Gp, Wp = _next_pow2(G + 1), self._pad_lanes(_next_pow2(W))
            row_widths.append([int(pts.shape[0]) for _, pts in entries])
            wps.append(Wp)

            glevel = np.full(Gp, n_levels, dtype=np.int32)  # sentinel rows
            lanemask = np.zeros((Gp, Wp), dtype=bool)
            accesses = (
                [("write", s.write)]
                + [(f"read{j}", r) for j, r in enumerate(s.reads)]
                + ([("guard", s.guard)] if s.guard is not None else [])
            )
            idx = {
                role: np.zeros((Gp, Wp), dtype=np.int32)
                for role, _ in accesses
            }
            oob = np.zeros((Gp, Wp), dtype=bool)
            guard_oob = np.zeros((Gp, Wp), dtype=bool)

            for gi, (lvl, pts) in enumerate(entries):
                glevel[gi] = lvl
                w = pts.shape[0]
                lanemask[gi, :w] = True
                if Wp > w:  # pad lanes repeat the first point (masked out)
                    pts = np.concatenate(
                        [pts, np.repeat(pts[:1], Wp - w, axis=0)]
                    )
                for role, ref in accesses:
                    a = ref.array
                    idx_inb = None
                    if is_indirect(ref):
                        # resolve the subscript against the index array's
                        # *contents* — the reason this table cache is keyed
                        # by _content_key on top of (bounds, layout)
                        iarr = ref.index.array
                        icoords = (
                            pts
                            + np.asarray(ref.index.offset_tuple(), np.int64)
                            - np.asarray(origin[iarr], np.int64)
                        )
                        ishp = np.asarray(shapes[iarr], np.int64)
                        idx_inb = np.all(
                            (icoords >= 0) & (icoords < ishp), axis=1
                        )
                        iflat = np.ravel_multi_index(
                            tuple(
                                np.clip(icoords[:, d], 0, shapes[iarr][d] - 1)
                                for d in range(icoords.shape[1])
                            ),
                            shapes[iarr],
                        )
                        ivals = dense.data[iarr].ravel()[iflat]
                        icov = dense.mask.get(iarr)
                        if icov is not None:
                            idx_inb &= icov.ravel()[iflat]
                        # astype truncates toward zero like the scalar
                        # executors' int()
                        coords = (ivals.astype(np.int64) + ref.offset)[
                            :, None
                        ] - np.asarray(origin[a], np.int64)
                    else:
                        coords = (
                            pts
                            + np.asarray(ref.offset_tuple(), np.int64)
                            - np.asarray(origin[a], np.int64)
                        )
                    shp = np.asarray(shapes[a], np.int64)
                    inb = np.all((coords >= 0) & (coords < shp), axis=1)
                    if idx_inb is not None:
                        inb &= idx_inb
                    flat = np.ravel_multi_index(
                        tuple(
                            np.clip(coords[:, d], 0, shapes[a][d] - 1)
                            for d in range(coords.shape[1])
                        ),
                        shapes[a],
                    )
                    # out-of-box lanes are redirected to the trash cell
                    flat = np.where(inb, flat, padded_sizes[a] - 1)
                    idx[role][gi] = flat.astype(np.int32)
                    bad = ~inb[:w]
                    if role == "guard":
                        guard_oob[gi, :w] |= bad
                    else:
                        oob[gi, :w] |= bad

            oob &= lanemask
            guard_oob &= lanemask
            # The guard access itself is evaluated unconditionally by the
            # sequential oracle, so a guard read outside the store is a
            # static error even for guarded statements.
            if guard_oob.any():
                raise KeyError(f"{s.name}: guard {_OOB_MSG}")
            if s.guard is None and oob.any():
                raise KeyError(f"{s.name}: {_OOB_MSG}")
            has_oob = bool(s.guard is not None and oob.any())

            cov_reads = tuple(r.array in dense.mask for r in s.reads)
            cov_guard = bool(
                s.guard is not None and s.guard.array in dense.mask
            )
            cov_write = s.write.array in dense.mask

            stmt_statics.append(
                _StmtStatic(
                    name=s.name,
                    write=s.write.array,
                    reads=tuple(r.array for r in s.reads),
                    guard=s.guard.array if s.guard is not None else None,
                    has_oob=has_oob,
                    cov_reads=cov_reads,
                    cov_guard=cov_guard,
                    cov_write=cov_write,
                    use_cond=self._use_cond(Wp),
                )
            )
            table = {
                "glevel": glevel,
                "lanemask": lanemask,
                "widx": idx["write"],
            }
            table["ridx"] = tuple(
                idx[f"read{j}"] for j in range(len(s.reads))
            )
            if s.guard is not None:
                table["gidx"] = idx["guard"]
            if has_oob:
                table["oob"] = oob
            tables.append(table)

        # Segment hybrid schedules AND inspect schedules: the band detector
        # only looks at per-level (statement, row) lockstep runs, which is
        # strategy-agnostic — an inspector-scheduled serialized chain lowers
        # to the same nested-fori recurrence band a chunked DOACROSS does,
        # instead of paying the generic per-level cursor dispatcher.
        segments, seg_dyn = None, ()
        if (
            sched.scc is not None and sched.scc.recurrences
        ) or instance_edges is not None:
            segments, seg_dyn = self._segment_levels(
                program, sched, n_levels, len(program.statements)
            )
            seg_dyn = self._split_band_widths(
                segments, seg_dyn, row_widths, wps
            )

        static = self._make_static(tuple(stmt_statics), segments)
        # The trace identity, computed host-side: everything jax's jit cache
        # keys a trace on — the statics plus the bucketed argument shapes
        # (level tables, padded store/coverage buffers, segment scalars).
        # Per-bounds *values* (n_levels, table contents, seg_dyn contents)
        # are traced arguments and deliberately absent.
        bucket = (
            static,
            tuple(
                tuple(
                    sorted(
                        (
                            role,
                            tuple(a.shape for a in arr)
                            if isinstance(arr, tuple)
                            else arr.shape,
                        )
                        for role, arr in t.items()
                    )
                )
                for t in tables
            ),
            tuple(sorted(padded_sizes.items())),
            sparse,
            tuple(d.shape for d in seg_dyn),
        )

        return PreparedCase(
            static=static,
            n_levels=n_levels,
            tables=tuple(tables),
            arrays=arrays,
            origin=origin,
            shapes=shapes,
            flat_sizes=flat_sizes,
            padded_sizes=padded_sizes,
            sparse=sparse,
            written=written,
            schedule=sched,
            seg_dyn=seg_dyn,
            bucket=bucket,
            lanes_live=sched.instances,
            lanes_padded=sum(len(w) * wp for w, wp in zip(row_widths, wps)),
        )

    # Minimum run of uniform levels worth collapsing into a nested loop —
    # below this the generic dispatcher's per-level cost doesn't matter.
    REC_BAND_MIN = 4
    # Most bands one case lowers: each compiles a nested loop of its own
    # (2·L+1 of them with the width ladder), so compile time grows with
    # their count; past the longest MAX_BANDS, a band's levels go to the
    # generic dispatcher.
    MAX_BANDS = 4

    def _band_rungs(self, wpb: int) -> int:
        """Width-ladder depth for a recurrence band of padded width
        ``wpb``: the number of halvings (≤ ``WIDTH_LADDER_RUNGS``) whose
        narrowest rung still holds ``WIDTH_LADDER_MIN`` lanes.  The sharded
        artifact overrides this to 0 (its per-shard lane slicing needs the
        full padded width).  Reads the module knobs late so a bench can
        pin the ladder off for an unsplit control build."""

        rungs = 0
        while (
            rungs < WIDTH_LADDER_RUNGS
            and (wpb >> (rungs + 1)) >= WIDTH_LADDER_MIN
        ):
            rungs += 1
        return rungs

    def _split_band_widths(
        self,
        segments: Tuple[Tuple, ...],
        seg_dyn: Tuple[np.ndarray, ...],
        row_widths: List[List[int]],
        wps: List[int],
    ) -> Tuple[np.ndarray, ...]:
        """Append width-ladder cut points to each recurrence band's dynamic
        vector (ROADMAP 3b).

        A skewed diamond's band ramps up to its widest diagonal and back
        down, but every level pays for the *widest* statement row because
        the whole band shares one padded lane count.  For a ladder of
        ascending rung widths ``w_1 < … < w_L < wpb`` this computes, per
        rung, the maximal prefix ``P_i`` (and suffix start ``Q_i``) of band
        rows whose actual lane counts all fit ``w_i`` — monotone cuts
        ``0 ≤ P_1 ≤ … ≤ P_L ≤ Q_L ≤ … ≤ Q_1 ≤ n`` appended as ``[P_1…P_L,
        Q_L…Q_1]`` — so the executor can run the ramps at sliced lane
        widths and only the plateau at full width.  Lanes sliced away are
        pure padding (mask-false, repeat-first-point, trash-scattered), so
        bit-equality is structural, not numerical luck.

        The cut *values* ride in the traced ``seg_dyn`` vector; only the
        ladder depth L changes the vector's shape, and L is a function of
        the padded band width — already a bucket component — so the
        four-level cache and the zero-re-trace property are preserved.
        Uniform bands (every row as wide as the plateau) append nothing
        and keep today's trace byte-for-byte.
        """

        out = []
        for seg, dyn in zip(segments, seg_dyn):
            if seg[0] != "rec":
                out.append(dyn)
                continue
            stmt_ks = seg[1]
            n = int(dyn[0])
            row0 = [int(r) for r in dyn[1:]]
            wpb = max(wps[k] for k in stmt_ks)
            rungs = self._band_rungs(wpb)

            def fits(t: int, w: int) -> bool:
                return all(
                    row_widths[k][row0[j] + t] <= min(w, wps[k])
                    for j, k in enumerate(stmt_ks)
                )

            ws = [wpb >> (rungs - i) for i in range(rungs)]
            cuts_p = []
            for w in ws:
                p = cuts_p[-1] if cuts_p else 0  # prefixes are monotone
                while p < n and fits(p, w):
                    p += 1
                cuts_p.append(p)
            cuts_q = []
            for w in ws:
                q = cuts_q[-1] if cuts_q else n  # suffixes are monotone
                while q > cuts_p[-1] and fits(q - 1, w):
                    q -= 1
                cuts_q.append(q)
            if rungs == 0 or (cuts_p[-1] == 0 and cuts_q[-1] == n):
                # degenerate ladder (a uniform band): keep the un-split
                # vector so the trace — and the bucket — match today's
                out.append(dyn)
                continue
            extra = cuts_p + list(reversed(cuts_q))
            out.append(
                np.concatenate(
                    [dyn, np.asarray(extra, dtype=np.int32)]
                )
            )
        return tuple(out)

    @staticmethod
    def _segment_levels(
        program: LoopProgram, sched, n_levels: int, n_stmts: int
    ) -> Tuple[Tuple[Tuple, ...], Tuple[np.ndarray, ...]]:
        """Partition the level sequence into wave segments + recurrence bands.

        A band is a maximal run of ≥ :attr:`REC_BAND_MIN` levels whose
        active (statement, table-row) pairs advance in lockstep — exactly
        what a chunked recurrence (plus any acyclic groups pipelined against
        it) produces.  Sound regardless of which statements land in a band:
        same-level groups of different scheduling units are independent by
        construction, and the band executes them in lexical order like the
        generic dispatcher.

        Returns ``(skeleton, seg_dyn)``: the bounds-free segment skeleton
        that goes into :class:`_CaseStatic` plus one ``int32`` scalar vector
        per segment (``[lo, hi, cursors0…]`` for waves, ``[n_chunks,
        row0…]`` for bands) that rides as a traced jit argument — the
        static/dynamic split that lets every bounds in a bucket share one
        trace.
        """

        import bisect

        stmt_index = {s.name: k for k, s in enumerate(program.statements)}
        level_active: List[List[Tuple[int, int]]] = [
            [] for _ in range(n_levels)
        ]
        rows_seen = [0] * n_stmts
        stmt_levels: List[List[int]] = [[] for _ in range(n_stmts)]
        for lvl, groups in enumerate(sched.levels):
            for g in groups:
                k = stmt_index[g.statement]
                level_active[lvl].append((k, rows_seen[k]))
                stmt_levels[k].append(lvl)
                rows_seen[k] += 1
        for active in level_active:
            active.sort()  # lexical statement order (groups already are)

        def cursors_at(level: int) -> Tuple[int, ...]:
            return tuple(
                bisect.bisect_left(stmt_levels[k], level)
                for k in range(n_stmts)
            )

        skeleton: List[Tuple] = []
        seg_dyn: List[np.ndarray] = []

        def wave(lo: int, hi: int) -> None:
            skeleton.append(("wave",))
            seg_dyn.append(
                np.asarray([lo, hi, *cursors_at(lo)], dtype=np.int32)
            )

        bands = []
        L = 0
        while L < n_levels:
            base = level_active[L]
            run = 1
            while L + run < n_levels and len(level_active[L + run]) == len(
                base
            ) and all(
                nk == bk and nr == br + run
                for (nk, nr), (bk, br) in zip(level_active[L + run], base)
            ):
                run += 1
            if base and run >= CompiledProgram.REC_BAND_MIN:
                bands.append((L, run, base))
            L += run
        longest = sorted(bands, key=lambda b: -b[1])
        wave_start = 0
        for L, run, base in sorted(longest[: CompiledProgram.MAX_BANDS]):
            if wave_start < L:
                wave(wave_start, L)
            skeleton.append(("rec", tuple(k for k, _ in base)))
            seg_dyn.append(
                np.asarray([run, *(r0 for _, r0 in base)], dtype=np.int32)
            )
            wave_start = L + run
        if wave_start < n_levels:
            wave(wave_start, n_levels)
        return tuple(skeleton), tuple(seg_dyn)

    # ------------------------------------------------------------------ #
    # The traced executable
    # ------------------------------------------------------------------ #

    def _exec(
        self, static: _CaseStatic, n_levels, seg_dyn, tables, store,
        coverage, bad, opaque_zero,
    ):
        import jax.numpy as jnp
        from jax import lax

        # this Python body runs exactly once per jax trace — the counter IS
        # the re-trace metric the serving layer and the sustained-traffic
        # bench gate on (a warm bucket never re-enters here)
        self._trace_count += 1
        _metrics.counter("xla.traces").inc()

        K = len(static.stmts)

        def group_step(k, ss, c, store, coverage, bad, gate=None,
                       lane_cap=None):
            """Vectorized gather/compute/scatter of statement ``k``'s table
            row ``c``; returns (new write array, new coverage, bad flags).
            Read-only arrays are captured by closure — routing the whole
            store through here would force XLA to copy every array.

            ``lane_cap`` (a static int) restricts the step to the row's
            leading ``lane_cap`` lanes — the width-ladder rungs of a
            recurrence band's ramps use it to skip gathers/scatters on
            lanes that are provably padding there (mask-false, so skipping
            them is structural, not a numerical approximation)."""

            t = tables[k]

            def row(m):
                r = lax.dynamic_index_in_dim(m, c, axis=0, keepdims=False)
                return r if lane_cap is None else r[:lane_cap]

            lanes = row(t["lanemask"])
            if gate is not None:  # condless path: fold the active
                lanes = lanes & gate  # bit into the lane mask
            ridx = [row(ix) for ix in t["ridx"]]
            mask = lanes
            if ss.guard is not None:
                gix = row(t["gidx"])
                if ss.cov_guard:
                    bad = bad.at[1].set(
                        bad[1] | jnp.any(lanes & ~coverage[ss.guard][gix])
                    )
                mask = mask & (store[ss.guard][gix] > 0.0)
            for j, (a, ix) in enumerate(zip(ss.reads, ridx)):
                if ss.cov_reads[j]:
                    bad = bad.at[1].set(
                        bad[1] | jnp.any(mask & ~coverage[a][ix])
                    )
            if ss.has_oob:
                oob_row = row(t["oob"])
                bad = bad.at[0].set(bad[0] | jnp.any(mask & oob_row))
                mask = mask & ~oob_row
            vals = self._lane_values(
                k, ss, store, ridx, lanes.shape[0], opaque_zero
            )
            trash = store[ss.write].shape[0] - 1
            tgt = jnp.where(mask, row(t["widx"]), trash)
            new_write = store[ss.write].at[tgt].set(vals)
            new_cov = (
                coverage[ss.write].at[tgt].set(True) if ss.cov_write else ()
            )
            return (new_write, new_cov, bad)

        def level_body(level, carry):
            """Generic dispatcher: per-statement cursors + lax.cond."""

            store, coverage, cursors, bad = carry
            for k, ss in enumerate(static.stmts):
                c = cursors[k]
                active = (
                    lax.dynamic_index_in_dim(
                        tables[k]["glevel"], c, axis=0, keepdims=False
                    )
                    == level
                )

                # the cond returns only what the group writes (one array,
                # optionally its coverage, the flags)
                def run_group(k=k, ss=ss, c=c, bad=bad, store=store,
                              coverage=coverage):
                    return group_step(k, ss, c, store, coverage, bad)

                def skip_group(ss=ss, bad=bad, store=store,
                               coverage=coverage):
                    return (
                        store[ss.write],
                        coverage[ss.write] if ss.cov_write else (),
                        bad,
                    )

                if ss.use_cond:
                    new_write, new_cov, bad = lax.cond(
                        active, run_group, skip_group
                    )
                else:
                    new_write, new_cov, bad = group_step(
                        k, ss, c, store, coverage, bad, gate=active
                    )
                store = dict(store)
                store[ss.write] = new_write
                if ss.cov_write:
                    coverage = dict(coverage)
                    coverage[ss.write] = new_cov
                cursors = cursors.at[k].add(active.astype(jnp.int32))
            return (store, coverage, cursors, bad)

        if static.segments is None:
            store, coverage, _, bad = lax.fori_loop(
                0,
                n_levels,
                level_body,
                (store, coverage, jnp.zeros((K,), jnp.int32), bad),
            )
            return store, coverage, bad

        # Segmented form (hybrid schedules with recurrence SCCs): wave
        # segments keep the generic dispatcher; each recurrence band is its
        # own nested fori_loop with the store as the recurrence carry — no
        # cursors, no conds, only the band's statements in the body.  All
        # per-bounds scalars (extents, cursor bases, chunk counts, row
        # bases) arrive in the traced ``seg_dyn`` vectors, so the trace is
        # bounds-free: any iteration count in the bucket replays it.
        for seg, dyn in zip(static.segments, seg_dyn):
            if seg[0] == "wave":
                store, coverage, _, bad = lax.fori_loop(
                    dyn[0],
                    dyn[1],
                    level_body,
                    (store, coverage, dyn[2:].astype(jnp.int32), bad),
                )
            else:
                _tag, stmt_ks = seg
                J = len(stmt_ks)
                # Ladder depth, recovered from the dynamic vector's *shape*
                # ([run, J row bases, 2·L cut points]).  The shape is a
                # bucket component, so L is trace-stable — the module knob
                # WIDTH_LADDER_RUNGS never leaks into a warm trace.
                L = (dyn.shape[0] - 1 - J) // 2

                def rec_body(t, carry, stmt_ks=stmt_ks, dyn=dyn, cap=None):
                    store, coverage, bad = carry
                    for j, k in enumerate(stmt_ks):  # lexical stmt order
                        ss = static.stmts[k]
                        ck = (
                            None
                            if cap is None
                            or cap >= tables[k]["lanemask"].shape[1]
                            else cap
                        )
                        new_write, new_cov, bad = group_step(
                            k, ss, dyn[1 + j] + t, store, coverage, bad,
                            lane_cap=ck,
                        )
                        store = dict(store)
                        store[ss.write] = new_write
                        if ss.cov_write:
                            coverage = dict(coverage)
                            coverage[ss.write] = new_cov
                    return (store, coverage, bad)

                if L == 0:
                    store, coverage, bad = lax.fori_loop(
                        0, dyn[0], rec_body, (store, coverage, bad)
                    )
                else:
                    # Width ladder: 2·L+1 chained fori_loops over the band
                    # — ramp-up rungs at ascending lane caps, the plateau
                    # at full width, ramp-down rungs mirrored.  Ranges the
                    # ladder found empty are zero-trip at run time.
                    wpb = max(
                        tables[k]["lanemask"].shape[1] for k in stmt_ks
                    )
                    ws = [wpb >> (L - i) for i in range(L)]
                    caps = ws + [wpb] + list(reversed(ws))
                    edges = (
                        [0]
                        + [dyn[1 + J + i] for i in range(2 * L)]
                        + [dyn[0]]
                    )
                    for lo, hi, cap in zip(edges, edges[1:], caps):
                        store, coverage, bad = lax.fori_loop(
                            lo,
                            hi,
                            lambda t, carry, cap=cap: rec_body(
                                t, carry, cap=cap
                            ),
                            (store, coverage, bad),
                        )
        return store, coverage, bad

    # ------------------------------------------------------------------ #
    # Host-side execution wrapper
    # ------------------------------------------------------------------ #

    def device_args(self, case: PreparedCase, dense: _DenseStore) -> Tuple:
        """The arguments of ``self._jit`` after ``case.static`` that run
        ``case`` on ``dense``: segment scalars and level tables (converted
        once per case), the padded store and coverage buffers, the error
        flags and the opaque zero.  Call inside :func:`x64`."""

        import jax.numpy as jnp

        if case._device_tables is None:
            # conversion is idempotent, so a concurrent duplicate would
            # cost only a wasted copy; the lock keeps assignment clean
            with self._lock:
                if case._device_tables is None:
                    case._device_segdyn = tuple(
                        jnp.asarray(d) for d in case.seg_dyn
                    )
                    case._device_tables = tuple(
                        {
                            k: (
                                tuple(jnp.asarray(x) for x in v)
                                if isinstance(v, tuple)
                                else jnp.asarray(v)
                            )
                            for k, v in t.items()
                        }
                        for t in case.tables
                    )
        store = {}
        for a in case.arrays:
            flat = np.zeros(case.padded_sizes[a], dtype=np.float64)
            flat[: case.flat_sizes[a]] = dense.data[a].ravel()
            store[a] = jnp.asarray(flat)
        coverage = {}
        for a in case.sparse:
            cov = np.zeros(case.padded_sizes[a], dtype=bool)
            cov[: case.flat_sizes[a]] = dense.mask[a].ravel()
            coverage[a] = jnp.asarray(cov)
        return (
            case.n_levels,
            case._device_segdyn,
            case._device_tables,
            store,
            coverage,
            jnp.zeros((2,), bool),
            jnp.int64(0),
        )

    def execute(self, case: PreparedCase, dense: _DenseStore) -> WavefrontStats:
        """Run the artifact on ``dense`` (mutated in place with the result).

        Only the written arrays (``case.written``), and the coverage of the
        sparse ones among them, are copied back: every other array of
        ``dense`` keeps the host values it came in with.
        """

        # bucket accounting before dispatch: a fresh trace identity is the
        # only thing that may legitimately re-enter the tracer
        with self._lock:
            new_bucket = case.bucket not in self._buckets
            if new_bucket:
                self._buckets.add(case.bucket)
        _metrics.counter(
            "xla.bucket_misses" if new_bucket else "xla.bucket_hits"
        ).inc()
        _metrics.counter("xla.levels").inc(case.n_levels)
        _metrics.counter("xla.lanes_live").inc(case.lanes_live)
        _metrics.counter("xla.lanes_padded").inc(case.lanes_padded)

        with x64():
            with _trace.span("xla.to_device"):
                args = self.device_args(case, dense)
            # host-side band timing: one level loop per jit call, so the
            # finest host-visible unit is the whole fused level sweep
            with _trace.span("xla.execute", levels=case.n_levels):
                out_store, out_cov, bad = self._jit(case.static, *args)
                # block inside the span: the jit call returns futures, and
                # an unblocked exit would time dispatch, not execution
                bad = np.asarray(bad)
            # device→host conversion stays inside the x64 scope: jax helper
            # jits (e.g. unstack) would otherwise see f32 defaults
            with _trace.span("xla.to_host"):
                out_np = {
                    a: np.asarray(out_store[a])[: case.flat_sizes[a]].reshape(
                        case.shapes[a]
                    )
                    for a in case.written
                }
                cov_np = {
                    a: np.asarray(out_cov[a])[: case.flat_sizes[a]].reshape(
                        case.shapes[a]
                    )
                    for a in case.sparse
                    if a in out_np
                }
        if bad[0]:
            raise KeyError(_OOB_MSG)
        if bad[1]:
            raise KeyError(_HOLE_MSG)
        dense.data.update(out_np)
        dense.mask.update(cov_np)
        sched = case.schedule
        return WavefrontStats(
            levels=sched.depth,
            batched_ops=sched.batched_ops,
            instances=sched.instances,
            max_width=sched.max_width,
        )
