"""Compile the chip's programs for a described TPU v5e, with no chip.

Every Pallas kernel the repo keeps is lowered through Mosaic at the
widths ``chip_smoke.py`` runs (the six registry update kernels through the
engine's own update program, plus the DFT, correlation and attention
kernels), and the default XLA blue-path update compiles for CountMin and
HyperLogLog stacks of 131,072 rows. A compile that passes is not a chip
run: these tests catch what Mosaic or the TPU compiler refuses (block
layouts, in-kernel gathers, memory) before any chip time is spent.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several pytest workers only
the worker that runs this file may touch it.
"""
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import core
from repro.core import batched
from repro.kernels import ops
from repro.service import engine

_PALLAS_KINDS = ["countmin", "ams", "hyperloglog", "bloom", "fm", "rhp"]
_N_PROBE = 8
_TABLE = 1 << 12


@pytest.fixture(scope="module")
def smoke():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("chip_smoke", mod)
    spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """This process's backend is the CPU, where the wrappers default to
    interpret mode; force Mosaic lowering for the described chip."""
    monkeypatch.setenv("SDE_PALLAS_INTERPRET", "0")


def _update_args(kind, n_rows, t, sharding, *, sources):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)
    state = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                         jax.eval_shape(
                             lambda: batched.stacked_init(kind, n_rows)))
    args = [state, spec((_TABLE,), jnp.uint32), spec((_TABLE,), jnp.uint32),
            spec((_TABLE,), jnp.int32), spec((t,), jnp.uint32),
            spec((t,), jnp.uint32), spec((t,), jnp.uint32),
            spec((t,), jnp.float32), spec((t,), jnp.bool_)]
    if sources:
        args.append(spec((2,), jnp.int32))
    return args


def _compile_update(kind, backend, n_rows, t, sharding, *, sources):
    """Lower the engine's own blue-path program for one kind stack."""
    ops.evict_kind_caches(kind)       # no program traced for another mode
    try:
        fn = engine._update_fn(kind, backend, None, sources, _N_PROBE)
        return fn.lower(*_update_args(kind, n_rows, t, sharding,
                                      sources=sources)).compile()
    finally:
        ops.evict_kind_caches(kind)


def test_pallas_kind_list_matches_chip_smoke(smoke):
    assert [name for name, _ in smoke.PALLAS_KINDS] == _PALLAS_KINDS


@pytest.mark.parametrize("name", _PALLAS_KINDS)
def test_pallas_update_kernel_compiles_for_v5e(name, smoke, one_chip,
                                               mosaic):
    kind = core.make_kind(name, **dict(smoke.PALLAS_KINDS)[name])
    dep = smoke.FULL
    compiled = _compile_update(kind, "pallas", dep.pallas_rows,
                               dep.pallas_tuples, one_chip, sources=True)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the kernel updates the donated stack in place
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes


@pytest.mark.parametrize("name", ["countmin", "hyperloglog"])
def test_xla_blue_path_update_compiles_at_131072_rows(name, smoke,
                                                      one_chip):
    params = dict(countmin=smoke.CM, hyperloglog=smoke.HLL)[name]
    kind = core.make_kind(name, **params)
    dep = smoke.FULL
    compiled = _compile_update(kind, "xla", dep.n_streams, dep.window,
                               one_chip, sources=False)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes
    # state plus a copy of it must fit one 16 GB chip with room to spare
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


def test_sliding_dft_kernel_compiles_for_v5e(one_chip, mosaic):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    s, f = 5120, 8
    compiled = jax.jit(ops.dft_step).lower(
        f32(s, f), f32(s, f), f32(s), f32(s), f32(f), f32(f)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pairwise_corr_kernel_compiles_for_v5e(one_chip, mosaic):
    x = jax.ShapeDtypeStruct((5120, 8, 2), jnp.float32, sharding=one_chip)
    compiled = jax.jit(ops.corr_matrix).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles_for_v5e(one_chip, mosaic):
    q = jax.ShapeDtypeStruct((8, 512, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(ops.flash_attention).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()
