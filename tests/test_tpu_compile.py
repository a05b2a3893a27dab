"""The main path compiles for a TPU v5e chip that is described, not attached.

The TPU compiler is installed on CPU-only hosts too: it compiles for a chip
described by ``jax.experimental.topologies``.  These tests run the chip's
compiler over the fleet controller's hot programs at deployment widths —
the Pallas gram kernel, the streaming ``fleet_step`` on one chip and
sharded over the four chips of a host, and the gram-hoisted segment
program — so a kernel Mosaic refuses (an unaligned block, too much
VMEM) or a program that does not fit the chip's memory fails here, not on
the chip.  Nothing runs; results and times come only from a chip run
(``chip_smoke.py``).

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and pytest-xdist
workers each import every test file.  Keep these tests in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import (
    EngineConfig,
    FleetInputs,
    FleetStep,
    fleet_step,
    fleet_stream_init,
    run_fleet_gram,
)
from repro.distributed.sharding import FLEET_AXIS, FleetMesh
from repro.kernels.disagg_solve import disagg_gram

#: chip_smoke.py's fleet: 64 nodes, 18 Kalman steps of 30 windows, the
#: seven Table 2 functions plus the control-plane column, 60 init windows.
B, S, N_W, M, N_INIT = 64, 18, 30, 8, 60
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 topology (four chips), persistent cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache out.
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """A ``FleetMesh`` over the host's four described chips."""
    import numpy as np
    from jax.sharding import Mesh

    return FleetMesh(mesh=Mesh(np.asarray(topo.devices), (FLEET_AXIS,)))


def _shape(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile_all_highest(lowered):
    """Compile, after checking that no contraction runs at the TPU's
    default one-pass bf16 precision (the Pallas kernel's own dot sits
    inside its custom call and is pinned in ``kernels.disagg_solve``)."""
    dots = [l for l in lowered.as_text().splitlines() if "dot_general" in l]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in l for l in dots)
    return lowered.compile()


@pytest.mark.parametrize(
    "g,n,m",
    [(B * S, N_W, M), (B, N_INIT, M), (B, 512, 128)],
    ids=["step-blocks", "init-blocks", "wide"],
)
def test_disagg_gram_compiles(one_chip, g, n, m):
    compiled = disagg_gram.lower(
        _shape((g, n, m), one_chip), _shape((g, n), one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,m", [(64, 8), (256, 128)])
def test_fleet_step_compiles(one_chip, b, m):
    cfg = EngineConfig()
    state = jax.eval_shape(
        lambda x0: fleet_stream_init(x0, N_W, cfg),
        jax.ShapeDtypeStruct((b, m), jnp.float32),
    )
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), state
    )
    step = FleetStep(
        c=_shape((b, m), one_chip), w=_shape((b,), one_chip),
        a=_shape((b, m), one_chip), lat_sum=_shape((b, m), one_chip),
        lat_sumsq=_shape((b, m), one_chip),
    )
    compiled = _compile_all_highest(fleet_step.lower(state, step, config=cfg))
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_sharded_fleet_step_compiles(four_chips):
    """``fleet_step`` under ``shard_map`` at the four-chip cell's fleet,
    1,024 nodes, 256 per chip: every input in its node shards and no
    collective in the program."""
    b, m = 1024, 8
    node, rep = four_chips.node_sharding(), four_chips.replicated_sharding()
    cfg = EngineConfig()
    state = jax.eval_shape(
        lambda x0: fleet_stream_init(x0, N_W, cfg),
        jax.ShapeDtypeStruct((b, m), jnp.float32),
    )
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=node if s.ndim else rep),
        state,
    )
    step = FleetStep(
        c=_shape((b, m), node), w=_shape((b,), node), a=_shape((b, m), node),
        lat_sum=_shape((b, m), node), lat_sumsq=_shape((b, m), node),
    )
    compiled = _compile_all_highest(fleet_step.lower(state, step, config=cfg, mesh=four_chips))
    assert all(s == node for s in compiled.input_shardings[0][1] if s is not None)
    text = compiled.as_text()
    assert not any(op in text for op in ("all-reduce", "all-gather", "collective-permute",
                                         "all-to-all", "reduce-scatter"))
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_segment_program_compiles_with_kernel(one_chip, monkeypatch):
    """``run_fleet_gram(backend="auto")`` at chip_smoke.py's phase B shapes.

    The engine picks the Pallas kernel from ``jax.default_backend()``,
    which is the CPU here: the test steers that choice to the TPU branch.
    """
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = EngineConfig(backend="auto")
    inputs = FleetInputs(
        c=_shape((B, S, N_W, M), one_chip), w=_shape((B, S, N_W), one_chip),
        a=_shape((B, S, M), one_chip), lat_sum=_shape((B, S, M), one_chip),
        lat_sumsq=_shape((B, S, M), one_chip),
    )
    seg = jax.jit(lambda inp, ic, iw: run_fleet_gram(inp, cfg, init_c=ic, init_w=iw))
    compiled = _compile_all_highest(seg.lower(
        inputs, _shape((B, N_INIT, M), one_chip), _shape((B, N_INIT), one_chip)
    ))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert total < HBM_BYTES
