"""Tsunami source inversion (paper §4.3), in JAX.

The original: 2011 Tohoku tsunami, shallow-water equations with wetting &
drying solved by ADER-DG in ExaHyPE at two resolutions (smoothed 1.7e5 dof /
fully-resolved 1.7e7 dof), observed at two DART buoys; a 3-level MLDA sampler
(GP emulator <- smoothed <- fully-resolved) infers the source location.

This analogue solves the 1-D shallow-water equations (Rusanov finite volumes,
hydrostatic reconstruction for a well-balanced bathymetry source, wetting &
drying via a depth threshold) on a 400 km ocean-to-coast transect:
  * fine level: 2048 cells, fully-resolved bathymetry (shelf + ridge bumps),
  * coarse level: 512 cells, SMOOTHED bathymetry (paper's smoothed model),
  * source: initial free-surface displacement eta0 = A exp(-((x-x0)/25km)^2),
    theta = (x0 [km], A [m]) — the 2-d source parameterization.
Observables (matching the paper's GP figure): arrival time + max wave height
at two buoys (x = 150 km, 250 km) -> 4 outputs.

The 2-D sea (`GRIDS_2D`) solves the same equations on a 400 km x 400 km
grid at the paper's smoothed resolution: 240 x 240 cells x (h, hu, hv) =
172,800 dof, and 480 x 480 cells with the ridges at the finer level. The
transect's profile runs along x, its shelf edge and coast bent along y;
theta = (x0 [km], y0 [km]), the source location the paper infers, under a
2-D Gaussian of 2.5 m and 25 km; buoys at two (x, y) sites.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.races import named_lock
from repro.core import tracing
from repro.core.interface import (
    Capabilities,
    Model,
    next_pow2,
    pad_to_bucket,
    sens_fn_traceable,
)
from repro.kernels import swe1d, swe2d
from repro.kernels.swe2d.swe2d import G, H_DRY, face_flux, update, velocities


L_DOMAIN = 400e3  # m
T_END = 2600.0  # s
BUOYS_KM = (150.0, 250.0)
ARRIVAL_THRESH = 0.1  # m
CFL = 0.3
C_MAX = float(np.sqrt(G * 4100.0))  # fastest wave speed the time step allows for
# the 2-D sea
BUOYS_2D_KM = ((200.0, 140.0), (230.0, 270.0))  # (x, y)
COAST_BEND_KM = 20.0  # shelf edge and coast move by +-20 km, one period over y
SOURCE_AMP = 2.5  # m
SOURCE_WIDTH = 25e3  # m


def bathymetry(x: np.ndarray, smoothed: bool) -> np.ndarray:
    """Seafloor elevation b(x) [m]: -4000 m deep ocean, continental shelf at
    ~300 km, beach reaching +10 m at the coast. The fine level adds ridge
    bumps that the smoothed level filters out (paper's two bathymetries)."""
    xk = x / 1e3
    deep = -4000.0
    shelf = deep + (deep * -1 + -80.0) * _sigmoid((xk - 300.0) / 12.0)  # rise to -80
    beach = (10.0 - -80.0) * _sigmoid((xk - 385.0) / 4.0)
    b = shelf + beach
    if not smoothed:
        b = b + 60.0 * np.sin(xk / 7.0) * _sigmoid((xk - 120.0) / 30.0) * _sigmoid((280.0 - xk) / 30.0)
    return b


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-np.asarray(z, float)))


@jax.custom_jvp
def _sqrt_safe(x):
    """sqrt with a clamped derivative: the PRIMAL is exactly `jnp.sqrt`
    (forward results unchanged bit for bit), but d/dx is capped at
    1/(2*1e-3) so reverse-mode through the Rusanov wave speeds stays finite
    where a cell is dry (sqrt'(0) = inf would NaN the whole adjoint)."""
    return jnp.sqrt(x)


@_sqrt_safe.defjvp
def _sqrt_safe_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    # the rule computes y through _sqrt_safe ITSELF (same primal — it IS
    # jnp.sqrt) so differentiating the rule again, as forward-over-reverse
    # HVPs do, re-enters the clamped rule instead of hitting the raw
    # sqrt'(0) = inf at dry interfaces (0 * inf = NaN in the second-order
    # tangents)
    y = _sqrt_safe(x)
    return y, t * 0.5 / jnp.maximum(y, jnp.asarray(1e-3, y.dtype))


@lru_cache(maxsize=None)
def _bathymetry_cached(n_cells: int, smoothed: bool) -> np.ndarray:
    """b(x) on the n_cells grid, computed once per (n_cells, smoothed) rather
    than on every trace of the batch programs."""
    dx = L_DOMAIN / n_cells
    x = (np.arange(n_cells) + 0.5) * dx
    return np.asarray(bathymetry(x, smoothed), np.float32)


def _transect_step(b: jax.Array, dt_dx: float):
    """The 1-D time step on [cells, N] lanes of (h, hu) over the bathymetry
    b [cells, 1]: desingularised velocity, hydrostatic reconstruction
    against the face bathymetry max(b_L, b_R) (Audusse et al., well-balanced
    with wetting and drying), Rusanov fluxes, the well-balanced momentum
    correction, reflective walls, and the positivity / dry-cell limiter,
    from the functions `repro.kernels.swe1d` shares with its kernel.
    `repro.kernels.swe.ref.swe_step_ref` is its oracle, bit-equal under
    `jit`."""
    bL, bR = b[:-1], b[1:]
    bstar = jnp.maximum(bL, bR)

    def step(h, hu):
        u = swe1d.velocity(h, hu)
        # _sqrt_safe == jnp.sqrt in the primal; only the adjoint differs
        # (clamped at dry interfaces), keeping the step differentiable
        Fh, Fq, hsL, hsR = swe1d.face_flux(h[:-1], h[1:], bL, bR, bstar, u[:-1], u[1:],
                                           sqrt=_sqrt_safe)
        A, B = swe1d.well_balanced(Fq, h[:-1], h[1:], hsL, hsR)
        # flux divergence per cell; reflective walls (zero mass flux,
        # hydrostatic pressure G/2 h^2)
        div_h = jnp.concatenate([Fh[:1], Fh[1:] - Fh[:-1], -Fh[-1:]], 0)
        pL = 0.5 * G * h[:1] ** 2
        pR = 0.5 * G * h[-1:] ** 2
        div_hu = jnp.concatenate([A[:1] - pL, A[1:] - B[:-1], pR - B[-1:]], 0)
        return swe1d.update(h, hu, div_h, div_hu, dt_dx)

    return step


def _buoy_rows(n_cells: int) -> tuple:
    """The cells of the 1-D buoys on a transect of `n_cells` cells."""
    dx = L_DOMAIN / n_cells
    return tuple(int(bk * 1e3 / dx) for bk in BUOYS_KM)


def _transect_source(thetas, n_cells: int, smoothed: bool):
    """[N, 2] sources (x0 [km], A [m]) -> the initial depth [n_cells, N]:
    still water plus A exp(-((x - x0) / 25 km)^2) where it is wet."""
    dx = L_DOMAIN / n_cells
    x = jnp.asarray((np.arange(n_cells) + 0.5) * dx, jnp.float32)[:, None]
    h0s = jnp.maximum(-jnp.asarray(_bathymetry_cached(n_cells, smoothed))[:, None], 0.0)
    x0 = thetas[None, :, 0] * 1e3  # [1, N]
    amp = thetas[None, :, 1]
    eta0 = amp * jnp.exp(-(((x - x0) / 25e3) ** 2))  # [C, N]
    return jnp.maximum(h0s + eta0 * (h0s > H_DRY), 0.0)


def _transect_rows(mx, arr, dt: float):
    """(max elevation, arrival step), each [2, N] -> [N, 4] rows
    [a1, h1, a2, h2], arrival in minutes (T_END where the wave never
    came)."""
    arrival = jnp.where(arr >= 0, arr * (dt / 60.0), T_END / 60.0)
    return jnp.stack([arrival, mx], axis=2).transpose(1, 0, 2).reshape(mx.shape[1], 4)


@partial(jax.jit, static_argnames=("n_cells", "smoothed", "swe_impl"))
def _solve_batch(
    thetas: jax.Array, n_cells: int, smoothed: bool, swe_impl: str = "scan"
) -> jax.Array:
    """[N, 2] -> [N, 4]: ONE jitted program solving all N sources in lockstep,
    along the path `swe1d.transect_path` picks for the platform: on a TPU
    the whole time loop in one Pallas kernel (`_solve_kernel`), elsewhere
    the `lax.scan` of `_transect_step` (`_solve_scan`). Its derivatives are
    the scan's on every platform.

    `swe_impl` is ignored: it is kept, static, only so that wrappers in
    `tests/bench/faults.py` that pass it through still call this program."""
    return _transect_observables(thetas, n_cells, smoothed, swe1d.transect_path())


def _transect_observables(thetas, n_cells: int, smoothed: bool, path: str):
    """`_solve_batch` along `path` ("pallas" or "xla")."""
    if path == "pallas":
        return _solve_kernel(thetas, n_cells, smoothed)
    return _solve_scan(thetas, n_cells, smoothed)


@partial(jax.custom_jvp, nondiff_argnums=(1, 2))
def _solve_kernel(thetas: jax.Array, n_cells: int, smoothed: bool) -> jax.Array:
    """[N, 2] -> [N, 4] through the time-loop kernel (`swe1d.transect_solve`),
    chains on sublanes and cells on lanes; under a mesh each device solves
    the chains of its shard. Derivatives are the scan's (`jax.jvp` of
    `_solve_scan`), with its clamped adjoint and remat: only evaluate-only
    programs run the kernel."""
    grid = Grid(n_cells, smoothed=smoothed)
    h = _transect_source(thetas, n_cells, smoothed)
    mx, arr = swe1d.transect_solve(
        h.T, _bathymetry_cached(n_cells, smoothed), steps=grid.steps,
        lam=grid.dt / (L_DOMAIN / n_cells), buoys=_buoy_rows(n_cells), thresh=ARRIVAL_THRESH)
    return _transect_rows(mx, arr, grid.dt)


@_solve_kernel.defjvp
def _solve_kernel_jvp(n_cells, smoothed, primals, tangents):
    return jax.jvp(partial(_solve_scan, n_cells=n_cells, smoothed=smoothed), primals, tangents)


def _solve_scan(thetas: jax.Array, n_cells: int, smoothed: bool) -> jax.Array:
    """[N, 2] -> [N, 4] as a `lax.scan` of `_transect_step` over the time
    steps:

      * state is laid out [n_cells, N] (batch LAST): every stencil slice
        (`h[:-1]`, `h[1:]`) and boundary concatenate is then a contiguous
        memory op instead of a strided copy per lane;
      * the arrival-time / max-height observable reduction runs INSIDE the
        scan carry, so only [N, 4] ever leaves the device;
      * buoys are read with static row slices instead of a gather, and the
        face bathymetry is computed once, outside the loop."""
    grid = Grid(n_cells, smoothed=smoothed)
    buoys = _buoy_rows(n_cells)
    b = jnp.asarray(_bathymetry_cached(n_cells, smoothed))[:, None]  # [C, 1]
    h0s = jnp.maximum(-b, 0.0)
    advance = _transect_step(b, grid.dt / (L_DOMAIN / n_cells))
    h0_buoy = jnp.stack([h0s[r, 0] for r in buoys])  # [2]

    N = thetas.shape[0]
    h = _transect_source(thetas, n_cells, smoothed)
    hu = jnp.zeros_like(h)

    def step(carry, i):
        h, hu, mx, arr = carry
        h_new, hu_new = advance(h, hu)
        eta_b = jnp.stack([h_new[r] for r in buoys], 0) - h0_buoy[:, None]  # [2, N]
        mx = jnp.maximum(mx, eta_b)
        arr = jnp.where((jnp.abs(eta_b) > ARRIVAL_THRESH) & (arr < 0), i, arr)
        return (h_new, hu_new, mx, arr), None

    init = (h, hu, jnp.full((2, N), -jnp.inf), jnp.full((2, N), -1.0))
    # remat the step for reverse-mode: without it the VJP stores EVERY
    # intermediate of every step (~20 [cells, N] arrays x n_steps of
    # residuals); with it only the carry is kept and the step recomputes in
    # the backward sweep — ~2x the FLOPs for ~10x less memory traffic,
    # which is the binding constraint on CPU (measured ~1.7x faster VJP
    # and ~5x smaller footprint at [512, 8]). Forward-only runs are
    # untouched (checkpoint is an AD-time construct).
    (_, _, mx, arr), _ = jax.lax.scan(
        jax.checkpoint(step), init, jnp.arange(grid.steps, dtype=jnp.float32)
    )
    return _transect_rows(mx, arr, grid.dt)


@partial(jax.jit, static_argnames=("n_cells", "smoothed"))
def _vjp_batch(thetas: jax.Array, senss: jax.Array, n_cells: int, smoothed: bool):
    """[N, 2] x [N, 4] -> ([N, 4], [N, 2]): lockstep reverse-mode through
    `_solve_batch` — ONE jitted program computes the primal AND sens^T J for
    the whole wave in the same [cells, batch] layout (the Jacobian is
    block-diagonal across lanes, so the batch VJP IS the per-lane VJP).
    Note the arrival-time observables are piecewise constant in theta, so
    their gradient contribution is exactly zero; the max-height channels
    carry the signal. Reverse-mode stores the scan carry per step
    (~n_cells x N x 2 floats x n_steps), which is why gradient waves chunk
    narrower than evaluate waves."""
    y, vjp = jax.vjp(lambda th: _solve_batch(th, n_cells, smoothed), thetas)
    return y, vjp(jnp.asarray(senss, y.dtype))[0]


@partial(jax.jit, static_argnames=("n_cells", "smoothed"))
def _jvp_batch(thetas: jax.Array, vecs: jax.Array, n_cells: int, smoothed: bool):
    """[N, 2] x [N, 2] -> [N, 4]: lockstep forward-mode (J vec) through
    `_solve_batch` — tangents ride the same scan, no carry storage."""
    return jax.jvp(
        lambda th: _solve_batch(th, n_cells, smoothed), (thetas,),
        (jnp.asarray(vecs, thetas.dtype),),
    )[1]


@partial(jax.jit, static_argnames=("n_cells", "smoothed"))
def _hvp_batch(
    thetas: jax.Array, senss: jax.Array, vecs: jax.Array,
    n_cells: int, smoothed: bool,
):
    """[N, 2] x [N, 4] x [N, 2] -> [N, 2]: lockstep Hessian-vector products
    d/de [J(theta + e vec)^T sens] via REVERSE-over-forward: the tangent
    J v rides the scan forward (doubling the carry, no storage), then one
    reverse sweep through the remat'd scan differentiates sens . (J v).
    The lanes' Jacobians are block-diagonal, so the batch HVP is the
    per-lane HVP. Forward-over-reverse (jvp of the VJP) is the textbook
    alternative but NaNs here: transposing the scan's backward sweep
    re-enters the dry-interface kinks (`maximum(., 0)` against
    `sqrt'(0)`) on the saturated branch, where the second-order tangents
    hit 0 * inf — the reverse-over-forward order never materializes that
    branch."""
    dtype = thetas.dtype

    def directional(th):
        _, tang = jax.jvp(
            lambda t: _solve_batch(t, n_cells, smoothed), (th,),
            (jnp.asarray(vecs, dtype),),
        )
        return jnp.sum(jnp.asarray(senss, tang.dtype) * tang)

    return jax.grad(directional)(thetas)


class Grid(NamedTuple):
    """One level of the forward model: `nx` cells along the 1-D transect, or
    an `ny` x `nx` grid over the 2-D sea where `ny` > 0; the smoothed
    bathymetry or the fully resolved one (ridges); `t_end` seconds
    simulated (the 1-D transect always runs `T_END`)."""
    nx: int
    ny: int = 0
    smoothed: bool = True
    t_end: float = T_END

    @property
    def cells(self) -> int:
        return self.nx * max(self.ny, 1)

    @property
    def dt(self) -> float:
        dx = L_DOMAIN / self.nx
        if self.ny:
            dx = min(dx, L_DOMAIN / self.ny)
        return CFL * dx / C_MAX

    @property
    def steps(self) -> int:
        return int(self.t_end / self.dt)


@lru_cache(maxsize=None)
def _sea_bathymetry(nx: int, ny: int, smoothed: bool) -> np.ndarray:
    """b(x, y) [m] on the [ny, nx] grid of the 2-D sea: the transect's
    profile along x, shifted by COAST_BEND_KM * sin(2 pi y / L) so that the
    shelf edge and the coast (and the fine level's ridges) bend along y."""
    x = (np.arange(nx) + 0.5) * (L_DOMAIN / nx)
    y = (np.arange(ny) + 0.5) * (L_DOMAIN / ny)
    bend = COAST_BEND_KM * 1e3 * np.sin(2 * np.pi * y / L_DOMAIN)
    return np.asarray(bathymetry(x[None, :] - bend[:, None], smoothed), np.float32)


def _courant(grid: Grid) -> tuple:
    """(dt / dx, dt / dy) of a 2-D grid."""
    return grid.dt / (L_DOMAIN / grid.nx), grid.dt / (L_DOMAIN / grid.ny)


def _buoy_cells(grid: Grid) -> tuple:
    """(row, column) of each 2-D buoy site on `grid`."""
    dx, dy = L_DOMAIN / grid.nx, L_DOMAIN / grid.ny
    return tuple((int(yk * 1e3 / dy), int(xk * 1e3 / dx)) for xk, yk in BUOYS_2D_KM)


def _divergence(h, b, bstar, un, ut, axis: int):
    """Per-cell flux differences along `axis` (-1: x, -2: y) of the mass,
    the normal and the tangential momentum. The walls reflect: no mass or
    tangential flux through them, hydrostatic pressure G/2 h^2 on them."""
    lo, hi = slice(None, -1), slice(1, None)
    first, last = slice(None, 1), slice(-1, None)

    def cut(x, sl):
        return x[..., sl] if axis == -1 else x[..., sl, :]

    f_h, f_l, f_r, f_t = face_flux(cut(h, lo), cut(h, hi), cut(b, lo), cut(b, hi), bstar,
                                   cut(un, lo), cut(un, hi), cut(ut, lo), cut(ut, hi))

    def walls(f_first, inner, f_last):
        return jnp.concatenate([f_first, inner, f_last], axis)

    div_h = walls(cut(f_h, first), cut(f_h, hi) - cut(f_h, lo), -cut(f_h, last))
    div_n = walls(cut(f_l, first) - 0.5 * G * cut(h, first) ** 2, cut(f_l, hi) - cut(f_r, lo),
                  0.5 * G * cut(h, last) ** 2 - cut(f_r, last))
    div_t = walls(cut(f_t, first), cut(f_t, hi) - cut(f_t, lo), -cut(f_t, last))
    return div_h, div_n, div_t


def _sea_step(grid: Grid):
    """The 2-D time step on [N, ny, nx] lanes of (h, hu, hv): x faces carry
    u along their normal, y faces v; both update the state at once."""
    b = jnp.asarray(_sea_bathymetry(grid.nx, grid.ny, grid.smoothed))
    bstar_x = jnp.maximum(b[:, :-1], b[:, 1:])
    bstar_y = jnp.maximum(b[:-1, :], b[1:, :])
    lam_x, lam_y = _courant(grid)

    def step(h, hu, hv):
        u, v = velocities(h, hu, hv)
        dh_x, du_x, dv_x = _divergence(h, b, bstar_x, u, v, -1)
        dh_y, dv_y, du_y = _divergence(h, b, bstar_y, v, u, -2)
        return update(h, hu, hv, (dh_x, du_x, dv_x), (dh_y, du_y, dv_y), lam_x, lam_y)

    return step


def _sea_source(thetas, grid: Grid):
    """[N, 2] sources (x0, y0) [km] -> the initial depth [N, ny, nx]: still
    water plus SOURCE_AMP * exp(-r^2 / SOURCE_WIDTH^2) where it is wet."""
    b = jnp.asarray(_sea_bathymetry(grid.nx, grid.ny, grid.smoothed))
    x = jnp.asarray((np.arange(grid.nx) + 0.5) * (L_DOMAIN / grid.nx), jnp.float32)
    y = jnp.asarray((np.arange(grid.ny) + 0.5) * (L_DOMAIN / grid.ny), jnp.float32)
    x0 = thetas[:, 0, None, None] * 1e3
    y0 = thetas[:, 1, None, None] * 1e3
    r2 = (x[None, None, :] - x0) ** 2 + (y[None, :, None] - y0) ** 2
    eta0 = SOURCE_AMP * jnp.exp(-r2 / SOURCE_WIDTH**2)
    h0 = jnp.maximum(-b, 0.0)
    return jnp.maximum(h0 + eta0 * (h0 > H_DRY), 0.0)


@partial(jax.jit, static_argnames=("grid",))
def _solve_batch_2d(thetas: jax.Array, grid: Grid) -> jax.Array:
    """[N, 2] -> [N, 4]: ONE jitted program solving N sources on the 2-D sea
    in lockstep, along the path `swe2d.sea_path` picks for the platform and
    the grid: on a TPU the whole time loop in one Pallas kernel that keeps
    each lane's state in VMEM, elsewhere the jnp scan of `_sea_step`."""
    return _sea_observables(thetas, grid, swe2d.sea_path(grid.ny, grid.nx))


def _sea_observables(thetas: jax.Array, grid: Grid, path: str) -> jax.Array:
    """`_solve_batch_2d` along `path` ("pallas" or "xla"). Either way the
    arrival and maximum at the buoys reduce inside the time loop, so only
    [N, 4] leaves the device."""
    N = thetas.shape[0]
    h = _sea_source(thetas, grid)
    buoys = _buoy_cells(grid)
    if path == "pallas":
        lam_x, lam_y = _courant(grid)
        mx, arr = swe2d.sea_solve(h, _sea_bathymetry(grid.nx, grid.ny, grid.smoothed),
                                  steps=grid.steps, lam_x=lam_x, lam_y=lam_y, buoys=buoys,
                                  thresh=ARRIVAL_THRESH)
    else:
        mx, arr = _sea_scan(h, grid, buoys)
    arrival = jnp.where(arr >= 0, arr * (grid.dt / 60.0), grid.t_end / 60.0)
    return jnp.stack([arrival, mx], axis=2).transpose(1, 0, 2).reshape(N, 4)


def _sea_scan(h, grid: Grid, buoys: tuple):
    """The time loop as a jnp scan of `_sea_step` from the depth h [N, ny,
    nx] (x minor: the lane axis holds a row of the sea, whatever N is):
    (max elevation, first step above the threshold), each [buoys, N]."""
    N = h.shape[0]
    step = _sea_step(grid)
    h0 = jnp.maximum(-jnp.asarray(_sea_bathymetry(grid.nx, grid.ny, grid.smoothed)), 0.0)
    h0_buoy = jnp.stack([h0[r, c] for r, c in buoys])  # [2]

    def body(carry, i):
        h, hu, hv, mx, arr = carry
        h, hu, hv = step(h, hu, hv)
        eta_b = jnp.stack([h[:, r, c] for r, c in buoys], 0) - h0_buoy[:, None]  # [2, N]
        mx = jnp.maximum(mx, eta_b)
        arr = jnp.where((jnp.abs(eta_b) > ARRIVAL_THRESH) & (arr < 0), i, arr)
        return (h, hu, hv, mx, arr), None

    zero = jnp.zeros_like(h)
    init = (h, zero, zero, jnp.full((2, N), -jnp.inf), jnp.full((2, N), -1.0))
    (_, _, _, mx, arr), _ = jax.lax.scan(
        body, init, jnp.arange(grid.steps, dtype=jnp.float32))
    return mx, arr


# Chunked dispatch for every batched op (`TsunamiModel._wave`): concurrent
# jitted solves on power-of-2-wide chunks. Two effects stack: chunks stay cache-resident
# ([C, <=64] working sets), and PJRT CPU executes concurrent computations on
# separate cores — XLA does not parallelize inside a `while` loop body, so
# thread-level chunking is how a CPU batch actually uses all cores.
_CHUNK_MAX = 64
_CHUNK_MIN = 4
_executor: ThreadPoolExecutor | None = None
_executor_lock = named_lock("tsunami.executor")


def _chunk_executor() -> ThreadPoolExecutor:
    global _executor
    with _executor_lock:
        if _executor is None:
            _executor = ThreadPoolExecutor(
                max_workers=max(os.cpu_count() or 1, 1),
                thread_name_prefix="tsunami-batch",
            )
        return _executor


def chunk_plan(N: int, chunk_max: int = _CHUNK_MAX) -> list:
    """[(lo, hi, bucket), ...]: the chunks of a wave of N lanes, one per
    host core or fewer, each padded to a power-of-two `bucket` in
    [_CHUNK_MIN, chunk_max]."""
    workers = max(os.cpu_count() or 1, 1)
    chunk = int(np.clip(next_pow2(-(-N // workers)), _CHUNK_MIN, chunk_max))
    return [(lo, min(lo + chunk, N), next_pow2(max(min(chunk, N - lo), _CHUNK_MIN)))
            for lo in range(0, N, chunk)]


class TsunamiModel(Model):
    """UM-Bridge model: theta -> 4 observables, at the levels of `grids`.
    config: {"level": 0 (coarse/smoothed, default) | 1 (fully resolved)}.

    By default the 1-D transect (`N_CELLS`), theta = (x0_km, amplitude_m),
    with the capability-typed v2 surface: native batched evaluate AND native
    batched gradient/apply_jacobian (lockstep AD through the SWE solver),
    plus the fused value-and-gradient wave gradient-based samplers ride.
    `TsunamiModel(TsunamiModel.GRIDS_2D)` is the 2-D sea, theta = (x0_km,
    y0_km), with evaluate and evaluate_batch only."""

    N_CELLS = {0: 512, 1: 2048}
    #: the 2-D sea: the paper's smoothed level (240^2 cells x 3 fields =
    #: 172,800 dof) and a finer level with the ridges (480^2 x 3 = 691,200)
    GRIDS_2D = {0: Grid(240, 240, smoothed=True), 1: Grid(480, 480, smoothed=False)}
    # chunks + pads internally (see `_wave`) — dispatcher-level
    # pow2 padding would only add wasted solves on top
    batch_bucket = False
    #: chunk width of every derivative op's wave (evaluate: `_CHUNK_MAX`):
    #: reverse-mode stores the scan carry per step, so derivative lanes cost
    #: ~3x the memory of evaluate lanes
    GRAD_CHUNK_MAX = 16

    #: cap on cached fused specializations (one per distinct sens_fn object)
    MAX_FUSED_CACHE = 8

    def __init__(self, grids: dict | None = None):
        super().__init__("forward")
        #: level -> Grid; all 1-D (the transect of `N_CELLS`) or all 2-D
        self.grids = dict(grids) if grids is not None else {
            lv: Grid(n, smoothed=(lv == 0)) for lv, n in self.N_CELLS.items()}
        if len({g.ny > 0 for g in self.grids.values()}) != 1:
            raise ValueError("a TsunamiModel's levels are all 1-D or all 2-D")
        self.two_d = next(iter(self.grids.values())).ny > 0
        # the fabric/server dispatch waves from several threads at once
        self._lock = named_lock("tsunami.stats")
        self.stats = {lv: 0 for lv in self.grids}
        #: per level: lanes dispatched (padding included), padded lanes and
        #: chunk programs run
        self.chunk_stats = {lv: {"lanes": 0, "padded": 0, "programs": 0}
                            for lv in self.grids}
        self._vgrad_cache: "OrderedDict" = OrderedDict()

    def get_input_sizes(self, config=None):
        return [2]

    def get_output_sizes(self, config=None):
        return [4]

    def capabilities(self, config=None) -> Capabilities:
        if self.two_d:
            return Capabilities(evaluate=True, evaluate_batch=True)
        return Capabilities(
            evaluate=True, evaluate_batch=True,
            gradient=True, gradient_batch=True,
            apply_jacobian=True, apply_jacobian_batch=True,
            apply_hessian=True, apply_hessian_batch=True,
        )

    def _grid(self, op: str, config) -> tuple[int, Grid]:
        """(level, grid) that `config` names; the 2-D sea serves evaluate
        only."""
        level = int((config or {}).get("level", 0))
        grid = self.grids[level]
        if grid.ny and op != "evaluate":
            raise NotImplementedError(
                "the 2-D sea serves evaluate and evaluate_batch only")
        return level, grid

    def __call__(self, parameters, config=None):
        return [list(map(float, self.evaluate_batch([parameters[0]], config)[0]))]

    def padded_lanes(self) -> int:
        """Lanes added by chunk padding, over every wave so far."""
        with self._lock:
            return sum(c["padded"] for c in self.chunk_stats.values())

    def _map_chunks(self, level: int, N: int, chunk_max: int, solve, kernel: str) -> list:
        """[solve(lo, hi, bucket), ...] over the chunks of a wave of N lanes:
        lanes [lo, hi) padded to `bucket`, a power of two, so the jit cache
        holds a handful of shapes per level. Several chunks run CONCURRENTLY
        on the host executor (PJRT CPU runs concurrent programs on separate
        cores), each in a `model.chunk` span under the caller's span that
        names the path `solve` takes (`kernel`: "pallas" or "xla")."""
        plan = chunk_plan(N, chunk_max)
        lanes = sum(bucket for _, _, bucket in plan)
        with self._lock:
            self.stats[level] += N
            counts = self.chunk_stats[level]
            counts["lanes"] += lanes
            counts["padded"] += lanes - N
            counts["programs"] += len(plan)
        parent = tracing.current()
        grid = self.grids[level]

        def run(part):
            lo, hi, bucket = part
            with tracing.span("model.chunk", parent=parent, level=level,
                              lanes=bucket, padded=bucket - (hi - lo),
                              cells=grid.cells, steps=grid.steps, kernel=kernel):
                return solve(lo, hi, bucket)

        if len(plan) == 1:
            return [run(plan[0])]
        return list(_chunk_executor().map(run, plan))

    def _wave(self, op: str, config, operands, program):
        """One batched op over a wave: the operands' rows [N, .] in chunks
        (`_map_chunks`), each padded to its bucket and run as
        `program(grid, *chunk)`, waited for, trimmed and joined; a program
        that returns a tuple gives a tuple of joined outputs."""
        level, grid = self._grid(op, config)
        operands = [np.atleast_2d(np.asarray(x, np.float32)) for x in operands]
        chunk_max = _CHUNK_MAX if op == "evaluate" else self.GRAD_CHUNK_MAX
        if grid.ny:
            kernel = swe2d.sea_path(grid.ny, grid.nx)
        else:  # derivative programs are the scan's on every platform
            kernel = swe1d.transect_path() if op == "evaluate" else "xla"

        def solve(lo, hi, bucket):
            out = program(grid, *(jnp.asarray(pad_to_bucket(x[lo:hi], bucket)[0])
                                  for x in operands))
            outs = tracing.wait(out if isinstance(out, tuple) else (out,))
            return [np.asarray(o, float)[: hi - lo] for o in outs]

        parts = self._map_chunks(level, len(operands[0]), chunk_max, solve, kernel)
        joined = tuple(np.concatenate(col, axis=0) for col in zip(*parts))
        return joined if len(joined) > 1 else joined[0]

    def evaluate_batch(self, thetas, config=None) -> np.ndarray:
        """[N, 2] -> [N, 4] through the lockstep batch solver of the level's
        grid (1-D or 2-D), in chunks of at most 64 lanes."""
        def program(grid, th):
            if grid.ny:
                return _solve_batch_2d(th, grid)
            return _solve_batch(th, grid.nx, grid.smoothed)

        return self._wave("evaluate", config, [thetas], program)

    # -- batched derivative surface -----------------------------------------
    def gradient(self, out_wrt, in_wrt, parameters, sens, config=None):
        theta = np.asarray(parameters[in_wrt], float)
        sens4 = np.zeros(4)
        sens4[:] = np.asarray(sens, float)  # single output block
        return self.gradient_batch(theta[None, :], sens4[None, :], config)[0].tolist()

    def gradient_batch(self, thetas, senss, config=None) -> np.ndarray:
        """[N, 2] x [N, 4] -> [N, 2]: lockstep reverse-mode waves."""
        return self._wave("gradient", config, [thetas, senss],
                          lambda grid, th, s: _vjp_batch(th, s, grid.nx, grid.smoothed)[1])

    def apply_jacobian(self, out_wrt, in_wrt, parameters, vec, config=None):
        theta = np.asarray(parameters[in_wrt], float)
        return self.apply_jacobian_batch(
            theta[None, :], np.asarray(vec, float)[None, :], config
        )[0].tolist()

    def apply_jacobian_batch(self, thetas, vecs, config=None) -> np.ndarray:
        """[N, 2] x [N, 2] -> [N, 4]: lockstep forward-mode (JVP) waves."""
        return self._wave("apply_jacobian", config, [thetas, vecs],
                          lambda grid, th, v: _jvp_batch(th, v, grid.nx, grid.smoothed))

    def apply_hessian(self, out_wrt, in_wrt1, in_wrt2, parameters, sens, vec, config=None):
        theta = np.asarray(parameters[in_wrt1], float)
        sens4 = np.zeros(4)
        sens4[:] = np.asarray(sens, float)  # single output block
        return self.apply_hessian_batch(
            theta[None, :], sens4[None, :], np.asarray(vec, float)[None, :], config
        )[0].tolist()

    def apply_hessian_batch(self, thetas, senss, vecs, config=None) -> np.ndarray:
        """[N, 2] x [N, 4] x [N, 2] -> [N, 2]: lockstep HVP waves
        (reverse-over-forward through the batch solver)."""
        return self._wave("apply_hessian", config, [thetas, senss, vecs],
                          lambda grid, th, s, v: _hvp_batch(th, s, v, grid.nx, grid.smoothed))

    def value_and_gradient_batch(self, thetas, sens_fn, config=None):
        """Fused (ys, grads) in ONE jitted dispatch per chunk when `sens_fn`
        is jax-traceable (applied per output row via vmap inside the
        program); falls back to the two-wave base default otherwise.
        Traceability is probed abstractly up front (`sens_fn_traceable`), so
        a transient dispatch error never silently downgrades the fused path;
        the per-sens_fn program cache is LRU-bounded."""
        level, grid = self._grid("value_and_gradient", config)
        if not sens_fn_traceable(sens_fn, 4, jnp.float32):
            return super().value_and_gradient_batch(thetas, sens_fn, config)
        key = (level, sens_fn)
        with self._lock:
            if key not in self._vgrad_cache:
                @jax.jit
                def fused(th):
                    y, vjp = jax.vjp(lambda t: _solve_batch(t, grid.nx, grid.smoothed), th)
                    senss = jax.vmap(sens_fn)(y)
                    return y, vjp(jnp.asarray(senss, y.dtype))[0]
                self._vgrad_cache[key] = fused
                while len(self._vgrad_cache) > self.MAX_FUSED_CACHE:
                    self._vgrad_cache.popitem(last=False)
            self._vgrad_cache.move_to_end(key)
            fused_fn = self._vgrad_cache[key]
        return self._wave("value_and_gradient", config, [thetas],
                          lambda grid, th: fused_fn(th))
