"""Compile rehearsals for one TPU v5e chip.

The main path's programs are compiled at their real widths for a described
(not attached) v5e topology: the chip's compiler refuses here what it would
refuse on the chip, such as a kernel tile it cannot lay out or a program that
does not fit in device memory. Nothing runs, so these tests say nothing about
results or times.

Every compile for the described chip lives in this one file. The topology is
described inside a module fixture, never while a module is imported, so every
test worker collects the same tests and only the worker given this file loads
the TPU compiler.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.apps.tsunami import TsunamiModel, _hvp_batch, _solve_batch, _vjp_batch
from repro.kernels.swe import swe_step

HBM_BYTES = 16e9  # device memory of one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable compiled for a described chip is written to the
        # persistent cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)


@pytest.mark.parametrize("cells,lanes", [(512, 64), (2048, 128)])
def test_swe_kernel_compiles_for_v5e(one_chip, cells, lanes):
    step = jax.jit(partial(swe_step, dt_dx=1e-3, impl="pallas"))
    h = _shape(one_chip, cells, lanes)
    compiled = step.lower(h, h, _shape(one_chip, cells, 1)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_solve_batch_compiles_at_level1(one_chip):
    thetas = _shape(one_chip, 128, 2)
    compiled = _solve_batch.lower(
        thetas, TsunamiModel.N_CELLS[1], False, "pallas"
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return ma.temp_size_in_bytes + ma.argument_size_in_bytes


def test_vjp_batch_fits_one_chip_at_level1(one_chip):
    """A full gradient chunk at the fine level fits one chip's memory."""
    n = TsunamiModel.GRAD_CHUNK_MAX
    compiled = _vjp_batch.lower(
        _shape(one_chip, n, 2), _shape(one_chip, n, 4),
        TsunamiModel.N_CELLS[1], False,
    ).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_hvp_batch_fits_one_chip_at_level0(one_chip):
    """The served Hessian-vector wave's chunk at the coarse level fits."""
    n = TsunamiModel.GRAD_CHUNK_MAX
    compiled = _hvp_batch.lower(
        _shape(one_chip, n, 2), _shape(one_chip, n, 4), _shape(one_chip, n, 2),
        TsunamiModel.N_CELLS[0], True,
    ).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
