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
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, SingleDeviceSharding

from repro.apps import tsunami as ts
from repro.apps.tsunami import (
    TsunamiModel,
    _hvp_batch,
    _solve_batch,
    _solve_batch_2d,
    _vjp_batch,
)
from repro.distributed.sharding import ShardingCtx, chain_carry_shardings
from repro.kernels.swe2d import ops as swe2d_ops
from repro.kernels.swe2d import sea_kernel
from repro.uq import fused
from repro.uq.gp import _kernel

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


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return ma.temp_size_in_bytes + ma.argument_size_in_bytes


@pytest.mark.parametrize("level,lanes", [(0, 16), (1, 16), (1, 64)])
def test_solve_batch_compiles_for_v5e(one_chip, level, lanes):
    """The 1-D batch program the cells run, at the chunk width a 128-point
    wave takes on the chip's host (16) and at the widest evaluate chunk."""
    grid = TsunamiModel().grids[level]
    compiled = _solve_batch.lower(_shape(one_chip, lanes, 2), grid.nx, grid.smoothed).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


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


@pytest.mark.parametrize("level", [0, 1])
def test_2d_sea_compiles_for_v5e_at_a_16_lane_chunk(one_chip, level):
    """The 2-D sea's levels (240^2 smoothed, 480^2 with ridges) at the chunk
    width a 128-point wave takes on the chip's host."""
    compiled = _solve_batch_2d.lower(
        _shape(one_chip, 16, 2), TsunamiModel.GRIDS_2D[level]).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("level", [0, 1])
def test_2d_sea_kernel_compiles_for_v5e_at_a_16_lane_chunk(one_chip, level):
    """The time-loop kernel the chip runs for each 2-D level, with its
    padded width, the strip height of its shape and its VMEM request."""
    grid = TsunamiModel.GRIDS_2D[level]
    w = swe2d_ops.padded_width(grid.nx)
    lam_x, lam_y = ts._courant(grid)
    run = partial(sea_kernel, nx=grid.nx, strip=swe2d_ops.strip_rows(grid.ny, w),
                  steps=grid.steps, lam_x=lam_x, lam_y=lam_y, buoys=ts._buoy_cells(grid),
                  thresh=ts.ARRIVAL_THRESH)
    bath = _shape(one_chip, grid.ny + 8, w)
    compiled = jax.jit(run).lower(_shape(one_chip, 16, grid.ny, w), bath, bath, bath).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture()
def tpu_path(monkeypatch):
    """The described chip is not attached, so `jax.default_backend()` says
    "cpu": answer "tpu" for the program's path choice, as the chip would,
    and trace the 1-D program afresh on both sides of the answer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _solve_batch.clear_cache()
    yield
    _solve_batch.clear_cache()


@pytest.mark.parametrize("level,lanes", [(1, 128), (0, 16)])
def test_1d_kernel_compiles_for_v5e(one_chip, tpu_path, level, lanes):
    """The 1-D time-loop kernel at the widest wave of the fine level and at
    the chunk width a 128-point wave takes on the chip's host."""
    grid = TsunamiModel().grids[level]
    compiled = _solve_batch.lower(_shape(one_chip, lanes, 2), grid.nx, grid.smoothed).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_fused_block_on_four_chips_solves_each_chips_chains(topo, tpu_path):
    """The four-chip cell's fused block: 512 chains sharded 128 per chip,
    each chip's kernel on its own [128, 512] chains, and no collective
    gathering the chains in front of it."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    ctx, K = ShardingCtx(mesh), 512
    target = fused.gaussian_likelihood_target(
        partial(_solve_batch, n_cells=512, smoothed=True), np.ones(4), np.ones(4),
        ((30.0, 150.0), (0.5, 4.0)))
    step = fused._rwm_step(target, np.eye(2), jnp.ones(K, bool))
    carry = {"key": jax.random.key(0), "xs": jnp.zeros((K, 2)), "lps": jnp.zeros(K),
             "acc": jnp.zeros(K)}
    csh = chain_carry_shardings(ctx, carry, K)
    ysh = ctx.sharding(None, "batch")
    block = jax.jit(lambda c: jax.lax.scan(step, c, None, length=25),
                    in_shardings=(csh,), out_shardings=(csh, (ysh, ysh)))
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=csh[k])
              for k, v in carry.items()}
    with mesh:
        text = block.lower(shapes).compile().as_text()
    kernel = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernel) == 1 and "f32[128,512]" in kernel[0]
    assert not re.search(r"all-gather|all-to-all|all-reduce|collective-permute", text)


def test_gp_kernel_compiles_for_v5e(one_chip):
    """The GP level's cross-kernel at the campaign's block: 128 queries
    against 128 training points in two dimensions."""
    compiled = _kernel.lower(
        _shape(one_chip, 128, 2), _shape(one_chip, 128, 2), _shape(one_chip, 2),
        _shape(one_chip),
    ).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
