"""Compile-only guards for the TPU v5e: the packed commit kernels and the
engine's inner step at tinygpt-15m's published widths, compiled by the
TPU compiler for a described (not attached) v5e chip with the Pallas
kernels in compiled Mosaic mode (``interpret=False``).

Nothing here runs on a chip; a passing compile says the chip's compiler
accepts the tiling, VMEM use and memory footprint, not that results or
speeds are right (``chip_smoke.py`` is the on-chip check). The topology
is described inside a module fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import InnerOptConfig
from repro.core import packing
from repro.core.compression import _compiled_program
from repro.kernels import packed as pk
from repro.kernels.tiling import LANES
from repro.models import build_model
from repro.optim.adamw import init_adam
from repro.train.inner import _jitted_step, pseudo_gradient

K = 4                      # commit_batch of the fused flush
BATCH, SEQ = 8, 512        # chip_smoke.py's inner-step shape
HBM_BYTES = 16 * 2 ** 30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    return build_model(get_config("tinygpt-15m"))


@pytest.fixture(scope="module")
def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def layout(param_shapes):
    return packing.build_layout(param_shapes)


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_full_width_layout(layout):
    # 16,016,128 fp32 params in 43 blocks: the buffer every commit sweeps
    assert layout.total_elems == 16_016_128
    assert layout.n_blocks == 43
    assert layout.n_rows == 125_128


@pytest.mark.parametrize("with_stats", [False, True])
def test_correct_outer_compiles(one_chip, layout, with_stats):
    r = layout.n_rows
    buf = _on(one_chip, (r, LANES))
    col = _on(one_chip, (r, 1))
    _compile(functools.partial(
        pk.packed_correct_outer, eta=0.7, mu=0.9, rho=1.0,
        interpret=False, with_stats=with_stats),
        buf, buf, buf, col, col)


def test_row_stats_compiles(one_chip, layout):
    buf = _on(one_chip, (layout.n_rows, LANES))
    _compile(functools.partial(pk.packed_row_stats, interpret=False),
             buf, buf)


@pytest.mark.parametrize("with_stats", [False, True])
def test_multi_correct_outer_compiles(one_chip, layout, with_stats):
    r = layout.n_rows
    buf = _on(one_chip, (r, LANES))
    _compile(functools.partial(
        pk.packed_multi_correct_outer, eta=0.7, mu=0.9,
        rho=jnp.ones((K,), jnp.float32), interpret=False,
        with_stats=with_stats),
        buf, buf, _on(one_chip, (K, r, LANES)), _on(one_chip, (K, r, 1)),
        _on(one_chip, (K, r, 1)))


def test_multi_gram_compiles(one_chip, layout):
    r = layout.n_rows
    _compile(functools.partial(pk.packed_multi_gram,
                               ranges=layout.block_row_ranges,
                               interpret=False),
             _on(one_chip, (r, LANES)), _on(one_chip, (K, r, LANES)))


def test_int8_roundtrip_kernels_compile(one_chip, layout):
    r = layout.n_rows
    buf = _on(one_chip, (r, LANES))
    col = _on(one_chip, (r, 1))
    _compile(functools.partial(pk.packed_rowabs, interpret=False), buf)
    _compile(functools.partial(pk.packed_quant, interpret=False), buf, col)
    _compile(functools.partial(pk.packed_dequant, interpret=False),
             _on(one_chip, (r, LANES), jnp.int8), col)


@pytest.mark.parametrize("carried_ef", [False, True])
def test_int8_roundtrip_program_compiles(one_chip, layout, param_shapes,
                                         carried_ef):
    """The worker's whole packed int8 round trip is one program: its
    three Mosaic kernels, and the scale's true division kept."""
    delta = jax.tree.map(lambda s: _on(one_chip, s.shape), param_shapes)
    ef = _on(one_chip, (layout.n_rows, LANES)) if carried_ef else None
    text = _compiled_program.lower(delta, ef, layout, False,
                                   None).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " divide(" in text


def test_inner_step_compiles(one_chip, model, param_shapes):
    place = lambda t: jax.tree.map(
        lambda s: _on(one_chip, s.shape, s.dtype), t)
    params = place(param_shapes)
    opt = place(jax.eval_shape(init_adam, param_shapes))
    batch = {"tokens": _on(one_chip, (BATCH, SEQ), jnp.int32),
             "labels": _on(one_chip, (BATCH, SEQ), jnp.int32)}
    step = _jitted_step(model, InnerOptConfig(warmup_steps=2,
                                              total_steps=32))
    compiled = step.lower(params, opt, batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_pseudo_gradient_compiles(one_chip, layout, param_shapes):
    """theta_bar - theta_H is one program over the whole model: its
    output is the fp32 delta of every parameter (small leaves padded to
    the chip's tiles), and nothing else."""
    params = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype),
                          param_shapes)
    compiled = pseudo_gradient.lower(params, params).compile()
    mem = compiled.memory_analysis()
    delta_bytes = 4 * layout.total_elems
    assert delta_bytes <= mem.output_size_in_bytes < 1.001 * delta_bytes
    assert mem.alias_size_in_bytes == 0
