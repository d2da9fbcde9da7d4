"""The TPU compiler's verdict on the served path, with no chip attached.

Each test compiles for a *described* TPU v5e (``jax.experimental.topologies``)
what ``chip_smoke.py`` runs on the chip: the level loop of every smoke
program at its smoke size (``repro.workloads``), and the Pallas matmul at
4096² in bf16.  Beside them, the level loop of the benchmark's HPCG SymGS
configuration at a 4^3 grid: three statements, two of them guarded.
Nothing runs, so results and times are not checked; what is checked is
that XLA:TPU accepts the programs, and that the float64 laundering of the
lowering is emitted for XLA:CPU only.

The topology is described inside module-scoped fixtures, never at import:
only one process at a time may load the TPU library, and the suite runs
under several workers.
"""

from __future__ import annotations

import pytest

from repro.workloads import SMOKE_PROGRAMS, seeded_store, smoke_program


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip, so keep it out."""

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


def _lowered(compiled, case, sharding):
    """``compiled._jit`` lowered for ``sharding``'s platform, with the
    argument shapes ``CompiledProgram.execute`` would pass."""

    import jax
    import jax.numpy as jnp

    from repro.compile.lowering import x64

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    with x64():
        tables = jax.tree.map(lambda a: spec(a.shape, a.dtype), case.tables)
        seg_dyn = tuple(spec(d.shape, d.dtype) for d in case.seg_dyn)
        store = {a: spec((case.padded_sizes[a],), jnp.float64)
                 for a in case.arrays}
        coverage = {a: spec((case.padded_sizes[a],), jnp.bool_)
                    for a in case.sparse}
        return compiled._jit.lower(
            case.static,
            spec((), jnp.int64),
            seg_dyn,
            tables,
            store,
            coverage,
            spec((2,), jnp.bool_),
            spec((), jnp.int64),
        )


def _program_and_store(name):
    if name == "hpcg_symgs":
        from test_hpcg_symgs import symgs

        _mod, _cfg, prog, options, store = symgs()
        return prog, options, store
    prog, options = smoke_program(name)
    return prog, options, seeded_store(prog, 0)


@pytest.mark.parametrize("name", SMOKE_PROGRAMS + ("hpcg_symgs",))
def test_level_loop_compiles_for_v5e(name, one_chip, no_persistent_cache):
    import jax

    from repro.core import plan
    from repro.core.wavefront import _DenseStore

    prog, options, store = _program_and_store(name)
    compiled = plan(prog, options).compile("xla").compiled
    case, _ = compiled.prepare(prog, _DenseStore(store))

    tpu = _lowered(compiled, case, one_chip)
    cpu = _lowered(
        compiled, case, jax.sharding.SingleDeviceSharding(jax.devices()[0])
    )
    # the laundering is the CPU's alone: XLA:TPU has no rule for it
    assert "bitcast_convert" in cpu.as_text()
    assert "bitcast_convert" not in tpu.as_text()
    tpu.compile()


def test_pipelined_matmul_compiles_for_v5e(one_chip, no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from repro.kernels.pipelined_matmul.kernel import pipelined_matmul

    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(pipelined_matmul).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
