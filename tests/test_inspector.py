"""Inspector-stage tests: exact instance graphs vs brute force, degenerate
index patterns, speculation rollback, and the full backend × deps-mode
bit-equality matrix over the non-affine corpus.

The inspector (:mod:`repro.core.inspector`) computes its graph in one
near-linear last-writer/readers sweep; the reference implementation here is
the O(n²) pairwise subscript comparison it replaces.  Two directions are
checked on every program:

  * soundness — every inspector edge is a genuine same-cell conflict pair;
  * sufficiency — a schedule layered from the inspector graph (plus the
    affine retained set) honors *every* pairwise conflict, including the
    transitively covered ones the sweep intentionally drops.

Semantics stays with the sequential oracle: each deps mode on each
registered backend must reproduce its store bit for bit.
"""

import dataclasses
import random

import pytest

from _hypothesis_compat import HealthCheck, given, settings, st
from oracle import assert_equivalent
from programs import NONAFFINE_PROGRAMS
from repro.core import (
    ArrayRef,
    IndirectRef,
    LoopProgram,
    PlanOptions,
    Statement,
    affine_retained,
    analyze,
    clear_inspector_cache,
    execution_backends,
    gather_scatter,
    histogram,
    index_content_digest,
    indexed_store,
    inspect_dependences,
    inspector_cache_stats,
    plan,
    ref_cell,
    run_sequential,
    sparse_matvec,
    speculation_violations,
)
from repro.core.inspector import resolved_guard_arrays
from repro.core.wavefront import schedule_levels

MODES = (None, "inspect", "speculate")


# ---------------------------------------------------------------------- #
# Reference implementation: O(n²) pairwise subscript comparison
# ---------------------------------------------------------------------- #

def live_instances(prog, store):
    """Every instance that can run: a guard over an array no statement
    writes is evaluated (not positive: the instance never runs); any other
    guard is conservatively taken as passing."""

    written = {s.write.array for s in prog.statements}
    out = []
    for it in prog.iterations():
        for s in prog.statements:
            g = s.guard
            if g is not None and g.array not in written:
                if not store[g.array][ref_cell(g, it, store)] > 0:
                    continue
            out.append((s, it))
    return out


def brute_force_pairs(prog, store):
    """All (earlier, later) pairs of live instances conflicting on an
    inspected array — the quadratic reference the inspector's sweep must
    agree with.  Same conventions as the inspector: guards over read-only
    arrays evaluated, other guards conservatively always read,
    same-iteration pairs omitted (program order covers them)."""

    targets = set(inspect_dependences(prog, store).arrays)
    accesses = []  # (instance, frozenset of read cells, write cell or None)
    for s, it in live_instances(prog, store):
        reads = list(s.reads)
        if s.guard is not None:
            reads.append(s.guard)
        rcells = frozenset(
            (r.array, ref_cell(r, it, store))
            for r in reads
            if r.array in targets
        )
        wcell = (
            (s.write.array, ref_cell(s.write, it, store))
            if s.write.array in targets
            else None
        )
        accesses.append(((s.name, it), rcells, wcell))
    pairs = set()
    for i, (u, ur, uw) in enumerate(accesses):
        for v, vr, vw in accesses[i + 1:]:
            if u[1] == v[1]:
                continue
            if (
                (uw is not None and (uw in vr or uw == vw))
                or (vw is not None and vw in ur)
            ):
                pairs.add((u, v))
    return pairs


def exact_schedule(prog, store):
    """The deps="inspect" schedule: affine retained set + instance edges,
    over the live instances."""

    p = plan(prog, PlanOptions(deps="inspect"))
    insp = inspect_dependences(prog, store)
    return schedule_levels(
        prog,
        list(affine_retained(p.retained)),
        instance_edges=insp.edges,
        inactive=insp.inactive,
    )


def assert_graph_cross_checks(prog, store):
    """Soundness + sufficiency of the inspector graph vs brute force, and
    the schedule laying out exactly the live instances."""

    insp = inspect_dependences(prog, store)
    pairs = brute_force_pairs(prog, store)
    extra = set(insp.edges) - pairs
    assert not extra, f"inspector invented non-conflicting edges: {extra}"
    sched = exact_schedule(prog, store)
    violated = speculation_violations(prog, sorted(pairs), sched.level_of())
    assert not violated, (
        f"exact schedule breaks pairwise conflicts: {violated[:5]}"
    )
    live = {(s.name, it) for s, it in live_instances(prog, store)}
    assert set(sched.level_of()) == live
    every = {(s.name, it) for it in prog.iterations() for s in prog.statements}
    assert insp.inactive == every - live


def assert_modes_bit_equal(prog, store=None, backends=None):
    init = {
        a: dict(c) for a, c in (store or prog.initial_store()).items()
    }
    oracle = run_sequential(prog, init)
    names = backends if backends is not None else tuple(execution_backends())
    for mode in MODES:
        p = plan(prog, PlanOptions(deps=mode))
        for backend in names:
            out = p.compile(backend).run(store=init)
            assert out == oracle, f"deps={mode!r} backend={backend} diverged"


# ---------------------------------------------------------------------- #
# Seeded random non-affine programs
# ---------------------------------------------------------------------- #

def random_nonaffine(seed, n_iter=6):
    """Random 1–3 statement program mixing indirect and affine accesses to
    a shared array — returns (program, store with random index contents)."""

    rng = random.Random(seed)
    index_arrays = ["i1", "i2"]
    stmts = []
    for k in range(rng.randint(1, 3)):
        if rng.random() < 0.6:
            write = IndirectRef("a", ArrayRef(rng.choice(index_arrays), 0))
        else:
            write = ArrayRef(rng.choice(["b", "c"]), 0)
        reads = []
        for _ in range(rng.randint(0, 2)):
            r = rng.random()
            if r < 0.4:
                reads.append(
                    IndirectRef("a", ArrayRef(rng.choice(index_arrays), 0))
                )
            elif r < 0.7:
                reads.append(ArrayRef("a", -rng.randint(0, 2)))
            else:
                reads.append(ArrayRef(rng.choice(["b", "c"]), -rng.randint(0, 1)))
        stmts.append(Statement(f"S{k+1}", write, tuple(reads)))
    prog = LoopProgram(statements=tuple(stmts), bounds=((0, n_iter),))
    if not prog.has_indirect():  # force at least one indirect access
        return random_nonaffine(seed + 10_000, n_iter)
    store = indexed_store(
        prog,
        {
            arr: [rng.randint(0, n_iter + 1) for _ in range(n_iter)]
            for arr in prog.index_arrays()
        },
    )
    return prog, store


# ---------------------------------------------------------------------- #
# Cross-check suites
# ---------------------------------------------------------------------- #

class TestGraphVsBruteForce:
    @pytest.mark.parametrize(
        "name,prog", NONAFFINE_PROGRAMS, ids=[n for n, _ in NONAFFINE_PROGRAMS]
    )
    def test_corpus_programs(self, name, prog):
        assert_graph_cross_checks(prog, prog.initial_store())

    @pytest.mark.parametrize("seed", range(20))
    def test_seeded_random_programs(self, seed):
        prog, store = random_nonaffine(seed)
        assert_graph_cross_checks(prog, store)
        # cheap executable check on every seed (full matrix below)
        assert_modes_bit_equal(prog, store, backends=("wavefront",))

    @pytest.mark.parametrize("seed", (0, 5, 10, 15))
    def test_seeded_random_all_backends(self, seed):
        prog, store = random_nonaffine(seed)
        assert_modes_bit_equal(prog, store)

    @given(st.integers(min_value=0, max_value=10_000))
    @settings(max_examples=25, deadline=None)
    def test_property_random_programs(self, seed):
        prog, store = random_nonaffine(seed)
        assert_graph_cross_checks(prog, store)

    @given(st.lists(st.integers(0, 7), min_size=8, max_size=8))
    @settings(max_examples=25, deadline=None)
    def test_property_histogram_bins(self, bins):
        prog = histogram(8)
        store = indexed_store(prog, {"bin": bins})
        assert_graph_cross_checks(prog, store)
        # exact depth equals the busiest bin's multiplicity
        depth = exact_schedule(prog, store).depth
        assert depth == max(bins.count(b) for b in set(bins))


# ---------------------------------------------------------------------- #
# Guards the inspector resolves (read-only guard arrays) and those it
# cannot (a guard array the loop writes)
# ---------------------------------------------------------------------- #

def random_guarded(seed, n_iter=8):
    """A seeded random non-affine program whose statements are guarded, at
    random, by the read-only flag array ``g`` (contents ±1 from the seed)."""

    prog, store = random_nonaffine(seed, n_iter)
    rng = random.Random(seed + 7)
    flags = [rng.random() < 0.6 for _ in prog.statements]
    flags[rng.randrange(len(flags))] = True
    prog = LoopProgram(
        statements=tuple(
            dataclasses.replace(s, guard=ArrayRef("g", 0)) if f else s
            for s, f in zip(prog.statements, flags)
        ),
        bounds=prog.bounds,
    )
    g = {(i,): rng.choice((-1.0, 1.0)) for i in range(n_iter)}
    return prog, dict(store, g=g)


def guarded_histogram(n=8):
    """``if (g[i] > 0) h[bin[i]] = f(h[bin[i]], w[i])``."""

    h = IndirectRef("h", ArrayRef("bin", 0))
    return LoopProgram(
        statements=(
            Statement("S1", h, (h, ArrayRef("w", 0)), guard=ArrayRef("g", 0)),
        ),
        bounds=((0, n),),
    )


class TestResolvedGuards:
    @pytest.mark.parametrize("seed", range(12))
    def test_live_graph_vs_brute_force(self, seed):
        prog, store = random_guarded(seed)
        assert resolved_guard_arrays(prog) == ("g",)
        assert inspect_dependences(prog, store).inactive
        assert_graph_cross_checks(prog, store)
        assert_modes_bit_equal(prog, store, backends=("wavefront",))

    @pytest.mark.parametrize("seed", (0, 5))
    def test_live_graph_all_backends(self, seed):
        prog, store = random_guarded(seed)
        assert_modes_bit_equal(prog, store)

    def test_guarded_out_instances_leave_every_level(self):
        prog = guarded_histogram()
        store = indexed_store(prog, {"bin": [2] * 8})
        store["g"] = {(i,): 1.0 if i in (1, 6) else 0.0 for i in range(8)}
        insp = inspect_dependences(prog, store)
        assert insp.edges == ((("S1", (1,)), ("S1", (6,))),)
        assert len(insp.inactive) == 6
        sched = exact_schedule(prog, store)
        assert sched.level_of() == {("S1", (1,)): 0, ("S1", (6,)): 1}
        assert_modes_bit_equal(prog, store)

    def test_an_inactive_instance_adds_no_level(self):
        """Program order runs S1 → S2 → S3 in each iteration; with every S2
        guarded out, S3 sits right after S1, and no level is left empty."""

        prog = LoopProgram(
            statements=(
                Statement("S1", IndirectRef("a", ArrayRef("idx", 0)), ()),
                Statement("S2", ArrayRef("b", 0), (), guard=ArrayRef("g", 0)),
                Statement(
                    "S3", ArrayRef("c", 0), (IndirectRef("a", ArrayRef("idx", 0)),)
                ),
            ),
            bounds=((0, 8),),
        )
        store = indexed_store(prog, {"idx": list(range(8))})
        store["g"] = {k: -1.0 for k in store["g"]}
        sched = exact_schedule(prog, store)
        assert sched.depth == 2
        assert [sorted({g.statement for g in lv}) for lv in sched.levels] == [
            ["S1"], ["S3"]
        ]
        assert_modes_bit_equal(prog, store)

    def test_written_guard_array_stays_conservative(self):
        # S2's guard reads b, which S1 writes: only the loop knows it
        prog = LoopProgram(
            statements=(
                Statement(
                    "S1",
                    ArrayRef("b", 0),
                    (IndirectRef("a", ArrayRef("idx", 0)),),
                ),
                Statement(
                    "S2",
                    IndirectRef("a", ArrayRef("perm", 0)),
                    (ArrayRef("b", 0),),
                    guard=ArrayRef("b", -1),
                ),
            ),
            bounds=((0, 8),),
        )
        store = indexed_store(
            prog, {"idx": [3, 1, 4, 1, 5, 2, 6, 5], "perm": [1, 2, 3, 4, 0, 5, 6, 7]}
        )
        store["b"] = {k: -1.0 for k in store["b"]}  # b[i-1] <= 0 at entry
        assert resolved_guard_arrays(prog) == ()
        insp = inspect_dependences(prog, store)
        assert insp.inactive == frozenset()
        assert exact_schedule(prog, store).instances == 16
        assert_graph_cross_checks(prog, store)
        assert_modes_bit_equal(prog, store)

    def test_guard_contents_key_the_cases_and_the_memo(self):
        """Equal index arrays, other guard contents: a case and an
        inspection of their own, both bit-equal to the oracle."""

        from repro.compile import clear_compile_cache

        prog = guarded_histogram()
        first = indexed_store(prog, {"bin": [0, 1, 0, 1, 0, 1, 0, 1]})
        first["g"] = {(i,): (-1.0) ** i for i in range(8)}
        second = dict(first, g={k: -v for k, v in first["g"].items()})
        assert index_content_digest(prog, first) != index_content_digest(
            prog, second
        )
        clear_compile_cache()
        clear_inspector_cache()
        ex = plan(prog, PlanOptions(deps="inspect")).compile("xla")
        for store in (first, second, first):
            init = {a: dict(c) for a, c in store.items()}
            assert ex.run(store=init) == run_sequential(prog, init)
        assert ex.compiled.prepared_cases == 2
        assert inspector_cache_stats()["misses"] == 2


# ---------------------------------------------------------------------- #
# Degenerate index patterns
# ---------------------------------------------------------------------- #

class TestDegeneratePatterns:
    def test_all_distinct_is_pure_doall(self):
        prog = histogram(8)
        store = indexed_store(prog, {"bin": list(range(8))})
        insp = inspect_dependences(prog, store)
        assert insp.conflict_free
        assert exact_schedule(prog, store).depth == 1
        assert_modes_bit_equal(prog, store)

    def test_all_same_fully_serializes(self):
        prog = histogram(8)
        store = indexed_store(prog, {"bin": [3] * 8})
        insp = inspect_dependences(prog, store)
        assert len(insp.edges) == 7  # the covering chain i -> i+1
        assert exact_schedule(prog, store).depth == 8
        assert_modes_bit_equal(prog, store)

    def test_permutation_is_pure_doall(self):
        prog = histogram(8)
        store = indexed_store(prog, {"bin": [5, 2, 7, 0, 4, 1, 6, 3]})
        assert inspect_dependences(prog, store).conflict_free
        assert exact_schedule(prog, store).depth == 1
        assert_modes_bit_equal(prog, store)

    def test_identity_gather_scatter_keeps_program_order_only(self):
        prog = gather_scatter(8)
        store = indexed_store(
            prog, {"idx": list(range(8)), "perm": list(range(8))}
        )
        # S1 reads a[i] and S2 writes a[i] in the SAME iteration — covered
        # by intra-iteration program order, so no instance edges remain
        assert inspect_dependences(prog, store).conflict_free
        assert exact_schedule(prog, store).depth == 2
        assert_modes_bit_equal(prog, store)

    def test_sparse_matvec_depth_tracks_row_multiplicity(self):
        prog = sparse_matvec(8)
        rows = [0, 1, 0, 2, 1, 0, 3, 2]  # row 0 hit three times
        store = indexed_store(
            prog, {"row": rows, "col": list(range(8))}
        )
        assert exact_schedule(prog, store).depth == 3
        assert_modes_bit_equal(prog, store)


# ---------------------------------------------------------------------- #
# Full oracle matrix: every registered backend × every deps mode
# ---------------------------------------------------------------------- #

class TestOracleMatrix:
    @pytest.mark.parametrize(
        "name,prog", NONAFFINE_PROGRAMS, ids=[n for n, _ in NONAFFINE_PROGRAMS]
    )
    def test_all_backends_all_methods(self, name, prog):
        """The standard differential harness (plan methods × backends ×
        naive/optimized) picks the non-affine corpus up unchanged."""

        assert_equivalent(prog)

    @pytest.mark.parametrize(
        "name,prog", NONAFFINE_PROGRAMS, ids=[n for n, _ in NONAFFINE_PROGRAMS]
    )
    def test_all_backends_all_deps_modes(self, name, prog):
        assert_modes_bit_equal(prog)

    def test_nonaffine_proxies_serialize_conservatively(self):
        """deps=None keeps the Δ=1 proxy chain: the schedule must be fully
        serial even when the runtime indices are conflict-free."""

        prog = histogram(6)
        store = indexed_store(prog, {"bin": list(range(6))})
        deps = analyze(prog)
        assert any(d.nonaffine for d in deps)
        wf = plan(prog).compile("wavefront").artifacts["wavefront"]
        assert wf.depth == 6
        assert_modes_bit_equal(prog, store, backends=("wavefront",))


# ---------------------------------------------------------------------- #
# Speculation: validation failure forces rollback, result stays bit-equal
# ---------------------------------------------------------------------- #

class TestSpeculationRollback:
    def _forced_violation(self):
        prog = histogram(8)
        store = indexed_store(prog, {"bin": [4] * 8})
        return prog, store

    def test_optimistic_schedule_is_actually_violated(self):
        """The forcing condition: the doall-optimistic schedule breaks the
        inspector graph, so the rollback path (not the happy path) is what
        the bit-equality below certifies."""

        prog, store = self._forced_violation()
        ex = plan(prog, PlanOptions(deps="speculate")).compile("wavefront")
        speculative = ex.artifacts["speculative"]
        assert speculative.depth == 1  # optimistic: everything level 0
        violated = speculation_violations(
            prog,
            inspect_dependences(prog, store).edges,
            speculative.level_of(),
        )
        assert violated, "expected the all-same pattern to violate doall"

    def test_rollback_bit_equal_on_wavefront(self):
        prog, store = self._forced_violation()
        init = {a: dict(c) for a, c in store.items()}
        out = (
            plan(prog, PlanOptions(deps="speculate"))
            .compile("wavefront")
            .run(store=init)
        )
        assert out == run_sequential(prog, init)

    def test_rollback_bit_equal_on_xla(self):
        prog, store = self._forced_violation()
        init = {a: dict(c) for a, c in store.items()}
        out = (
            plan(prog, PlanOptions(deps="speculate"))
            .compile("xla")
            .run(store=init)
        )
        assert out == run_sequential(prog, init)

    def test_validation_passes_without_conflicts(self):
        prog = histogram(8)
        store = indexed_store(prog, {"bin": list(range(8))})
        ex = plan(prog, PlanOptions(deps="speculate")).compile("wavefront")
        assert not speculation_violations(
            prog,
            inspect_dependences(prog, store).edges,
            ex.artifacts["speculative"].level_of(),
        )
        init = {a: dict(c) for a, c in store.items()}
        assert ex.run(store=init) == run_sequential(prog, init)


# ---------------------------------------------------------------------- #
# Cache placement and plumbing
# ---------------------------------------------------------------------- #

class TestInspectorPlumbing:
    def test_inspector_memo_hits_and_content_sensitivity(self):
        clear_inspector_cache()
        prog = histogram(8)
        s1 = indexed_store(prog, {"bin": list(range(8))})
        s2 = indexed_store(prog, {"bin": [0] * 8})
        r1 = inspect_dependences(prog, s1)
        r1b = inspect_dependences(prog, s1)
        r2 = inspect_dependences(prog, s2)
        assert r1 is r1b  # memo hit on identical contents
        assert inspector_cache_stats()["hits"] >= 1
        assert r1.conflict_free and not r2.conflict_free

    def test_structural_key_is_content_free_but_mode_aware(self):
        """Two stores with different index contents share one structural
        artifact; the deps knob (a structural option) splits it."""

        from repro.compile.structure import structural_key

        prog = histogram(8)
        retained = tuple(plan(prog).retained)
        base = structural_key(prog, retained, "doall", None, None, None, None)
        same = structural_key(prog, retained, "doall", None, None, None, None)
        inspect_key = structural_key(
            prog, retained, "doall", None, None, None, "inspect"
        )
        assert base == same
        assert base != inspect_key

    def test_unknown_deps_mode_rejected(self):
        with pytest.raises(ValueError, match="deps mode"):
            PlanOptions(deps="optimistic")

    def test_index_array_write_rejected(self):
        with pytest.raises(ValueError, match="index"):
            LoopProgram(
                statements=(
                    Statement(
                        "S1",
                        ArrayRef("bin", 0),
                        (IndirectRef("h", ArrayRef("bin", 0)),),
                    ),
                ),
                bounds=((0, 4),),
            )

    def test_affine_program_inspects_empty(self):
        from programs import DIFFERENTIAL_PROGRAMS

        for _name, prog in DIFFERENTIAL_PROGRAMS[:3]:
            insp = inspect_dependences(prog)
            assert insp.arrays == () and insp.conflict_free
