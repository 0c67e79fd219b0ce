"""The scheduling plane's device path and the decode step, compiled for
a described TPU v5e.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (tiling, VMEM, memory).  The topology is described in a module
fixture, never at import, and the tests skip where it cannot be.
Shapes are site scale: LLNL Quartz, 2,688 nodes x 2 sockets x 18
cores, |V| = 104,833 (104,960 lanes after padding to 128).  The decode
step is phi4-mini-3.8b's at registered widths, as the benchmark serves it:
batch 32, a 768-position cache.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
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


def _computations(hlo: str):
    """{name: (ROOT opcode, [(opcode, result type, called computation)])}
    of an HLO module's text, and the names of the computations fusions
    call."""
    comps, fused, name = {}, set(), None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = [None, []]
            continue
        op = re.match(r"\s*(ROOT )?%\S+ = (\S+) ([\w-]+)\(", line)
        if name is None or not op:
            continue
        calls = re.search(r"calls=(%[\w.\-]+)", line)
        calls = calls and calls.group(1)
        comps[name][1].append((op.group(3), op.group(2), calls))
        if op.group(1):
            comps[name][0] = op.group(3)
        if op.group(3) == "fusion":
            fused.add(calls)
    return comps, fused


def test_serve_step_updates_cache_in_place_for_v5e(one_chip):
    """The decode step at its real size writes only the new K/V rows into
    the donated cache: outside fusions, no op makes a buffer of a layer's
    cache or more except a dynamic-update-slice of the stack, and the
    program's temporaries stay under one layer's cache."""
    from repro.models.config import ShapeConfig
    from repro.models.model import make_model
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"),
                              tie_embeddings=True)
    model = make_model(cfg)
    B, S = 32, 768

    def on_chip(sd, dtype=None):
        return jax.ShapeDtypeStruct(sd.shape, dtype or sd.dtype,
                                    sharding=one_chip)
    params = jax.tree_util.tree_map(lambda sd: on_chip(sd, jnp.bfloat16),
                                    model.param_shapes())
    cache = jax.tree_util.tree_map(
        on_chip, model.cache_specs(ShapeConfig("serve", S, B, "decode")))
    compiled = jax.jit(model.serve_step, donate_argnums=(1,)).lower(
        params, cache, {"tokens": _i32((B, 1), one_chip)},
        _i32((), one_chip)).compile()

    layer_bytes = B * cfg.n_kv_heads * S * cfg.hd * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * cfg.n_layers * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes

    comps, fused = _computations(compiled.as_text())
    views = {"parameter", "get-tuple-element", "tuple", "bitcast"}
    big = []
    for name, (_, ops) in comps.items():
        if name in fused:
            continue
        for opcode, result, calls in ops:
            dims = re.match(r"\w+\[([\d,]*)\]", result)
            if dims is None or opcode in views:
                continue
            dims = [int(d) for d in dims.group(1).split(",") if d]
            if S not in dims or 2 * math.prod(dims) < layer_bytes:
                continue
            root = comps[calls][0] if calls in comps else opcode
            if root != "dynamic-update-slice":
                big.append((name, opcode, result))
    assert not big, big
