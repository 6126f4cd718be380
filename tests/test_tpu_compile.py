"""Compile-only checks for a TPU v5e at published widths.

Nothing here runs on a chip: each test lowers and compiles a program
for a *described* v5e (``topologies.get_topology_desc``), which runs
the TPU compiler and Mosaic (the Pallas TPU compiler) on this host.
That catches what interpret mode cannot: block shapes Mosaic refuses,
VMEM over-use, programs that do not fit the chip's memory.  A compile
that passes is not a chip run and says nothing about results or time.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, so under a
multi-worker pytest run only the worker given this file loads it.
The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.configs import get_config
from repro.kernels.crossbar_mac.kernel import crossbar_mac
from repro.models.model import build_model
from repro.serve.engine import make_window_step

_SLOTS, _CHUNK, _MAX_LEN, _PAGE = 4, 4, 160, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels resolve interpret mode from the host's backend (the CPU
    here); steer them to compile for the chip."""
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("b,n,rows_per_adc", [
    (8, 9728, 128),        # decode-width batch, MLP width, deep-net group
    (64, 152064, 256),     # prefill-chunk batch, LM head, expansion group
])
def test_crossbar_mac_compiles_at_published_widths(
        one_chip, compiled_kernels, b, n, rows_per_adc):
    k, s = 2560, 4
    lowered = jax.jit(lambda x, p, q, leak: crossbar_mac(
        x, p, q, leak, in_bits=8, adc_bits=8, bits_per_cell=1,
        rows_per_adc=rows_per_adc, block_b=b, block_n=128)).lower(
            *_on(one_chip, (jax.ShapeDtypeStruct((b, k), jnp.int32),
                            jax.ShapeDtypeStruct((s, k, n), jnp.int8),
                            jax.ShapeDtypeStruct((s, k, n), jnp.int8),
                            jax.ShapeDtypeStruct((), jnp.float32))))
    assert "tpu_custom_call" in lowered.compile().as_text()


def _window_args(model, params, tiles, sharding):
    n_pages = _SLOTS * _MAX_LEN // _PAGE
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        _SLOTS, _MAX_LEN, n_pages, _PAGE))
    return _on(sharding, (
        params, tiles, jax.ShapeDtypeStruct((_SLOTS, _CHUNK), jnp.int32),
        cache, jax.ShapeDtypeStruct((_SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32)))


def test_digital_window_step_compiles_at_published_widths(one_chip):
    """One layer of qwen3-4b at its published widths, from eval_shape
    shapes: the scheduler's window step fits one chip."""
    model = build_model(dataclasses.replace(get_config("qwen3-4b"),
                                            n_layers=1))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    compiled = make_window_step(model).lower(
        *_window_args(model, params, {}, one_chip)).compile()
    ma = compiled.memory_analysis()
    arg_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                    for x in jax.tree.leaves(params))
    assert ma.argument_size_in_bytes >= arg_bytes
    assert ma.temp_size_in_bytes < arg_bytes // 10


def test_crossbar_window_step_compiles_with_tiles_as_arguments(
        one_chip, compiled_kernels):
    """The crossbar backend's window step, with every linear on the
    compiled ``crossbar_mac`` and the tiles passed as arguments."""
    cfg = get_config("qwen3-4b", smoke=True)
    cfg = dataclasses.replace(
        cfg, backend="crossbar",
        xbar=dataclasses.replace(cfg.xbar, use_kernel=True))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ex = model.executor
    ex.program_params(params)
    tiles = ex.resident_tiles("A")
    compiled = make_window_step(model).lower(
        *_window_args(model, params, tiles, one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(tiles)
