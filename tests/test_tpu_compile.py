"""The scheduling plane's device path, compiled for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (tiling, VMEM, memory).  The topology is described in a module
fixture, never at import, and the tests skip where it cannot be.
Shapes are site scale: LLNL Quartz, 2,688 nodes x 2 sockets x 18
cores, |V| = 104,833 (104,960 lanes after padding to 128).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import flatgraph
from repro.kernels.feasibility import _feasible_pallas

V_SITE = 104_833
V_LANES = 104_960
LEVELS_SITE = (1, 2688, 2688 * 2, 2688 * 2 * 18)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        was = jax.config.jax_enable_compilation_cache
        # a described-chip executable can be written to the persistent
        # cache but never read back without a chip
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:      # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.mark.parametrize("n_rows", [8, 64])
def test_feasibility_kernel_compiles_for_v5e(one_chip, n_rows):
    col = _i32((n_rows, 1), one_chip)
    row = _i32((1, V_LANES), one_chip)
    compiled = _feasible_pallas.lower(
        col, col, col, col, _i32((n_rows, 8), one_chip),
        row, row, row, row, row, _i32((8, V_LANES), one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == \
        n_rows * V_LANES * 4


def test_aggregate_sweep_compiles_for_v5e(one_chip):
    levels = [_i32((n,), one_chip) for n in LEVELS_SITE]
    compiled = flatgraph._sweep_fn().lower(
        _i32((V_SITE, 4), one_chip), _i32((V_SITE,), one_chip),
        *levels).compile()
    assert compiled.out_info.shape == (V_SITE, 4)
