"""The 1-D transect's whole time loop in one Pallas TPU kernel.

The physics of one step is written once here: the desingularised velocity,
the Rusanov flux with hydrostatic reconstruction across a face, its
well-balanced momentum corrections, and the update with its positivity /
dry-cell limiter. The jnp step of `repro.apps.tsunami` (`_transect_step`)
and the kernel below both call them on their own operands, in the same
order of operations.

The state is laid out [chains, cells]: chains on sublanes, cells on lanes
(the cells padded up to a multiple of 128 are inert). The kernel's grid runs
over strips of `strip` chains. For one strip it loads the initial depth
once, carries (h, hu) through every time step in the loop, and writes only
the buoys' maximum elevation and arrival step. A cell's neighbour is a lane
roll of its row, masked at the walls (cell n_cells - 1 is the east wall).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.swe2d.swe2d import G, H_DRY, SQRT2

#: chains a strip holds at least: one (8, 128) tile of sublanes
SUBLANES = 8


def velocity(h, hu):
    """Desingularised velocity: finite at the shoreline."""
    h4 = h**4
    return SQRT2 * h * hu / jnp.sqrt(h4 + jnp.maximum(h, H_DRY) ** 4)


def face_flux(hL, hR, bL, bR, bstar, uL, uR, sqrt=jnp.sqrt):
    """Rusanov flux across faces from cell L to cell R, with hydrostatic
    reconstruction (Audusse et al.) against the face bathymetry `bstar`.
    Returns the mass and momentum fluxes and the reconstructed depths on
    each side. `sqrt` takes the wave speeds' square roots (the scan passes
    one whose adjoint is clamped where a face is dry; its primal is
    `jnp.sqrt`)."""
    hsL = jnp.maximum(hL + bL - bstar, 0.0)
    hsR = jnp.maximum(hR + bR - bstar, 0.0)
    mL, mR = hsL * uL, hsR * uR  # interface mass fluxes
    a = jnp.maximum(jnp.abs(uL) + sqrt(G * hsL), jnp.abs(uR) + sqrt(G * hsR))
    f_h = 0.5 * (mL + mR) - 0.5 * a * (hsR - hsL)
    f_q = 0.5 * ((mL * uL + 0.5 * G * hsL * hsL) + (mR * uR + 0.5 * G * hsR * hsR)) \
        - 0.5 * a * (mR - mL)
    return f_h, f_q, hsL, hsR


def well_balanced(f_q, hL, hR, hsL, hsR):
    """The momentum flux with its well-balanced pressure correction as cell
    L sees it and as cell R sees it. Apart from `face_flux`, so that the
    scan reads its depths afresh here, as its adjoint always has."""
    return f_q + 0.5 * G * (hL**2 - hsL**2), f_q + 0.5 * G * (hR**2 - hsR**2)


def update(h, hu, div_h, div_hu, lam):
    """The update from the flux differences: depth clipped at zero,
    momentum zeroed where the new depth is under H_DRY."""
    h_new = jnp.maximum(h - lam * div_h, 0.0)
    return h_new, jnp.where(h_new > H_DRY, hu - lam * div_hu, 0.0)


def _transect_kernel(h0_ref, b_ref, be_ref, bs_ref, obs_ref, *state_out, n_cells, steps,
                     lam, buoys, thresh):
    """One strip of chains: every step in the loop carry, then the buoys'
    lane tiles of [max elevation, arrival step] into `obs_ref` (and the
    final state into the optional state output)."""
    S, W = h0_ref.shape
    f32 = jnp.float32
    col = jax.lax.broadcasted_iota(jnp.int32, (S, W), 1)
    east_wall = col >= n_cells - 1
    west_wall = (col == 0) | (col >= n_cells)

    def east(a):  # a[:, i + 1]
        return pltpu.roll(a, W - 1, 1)

    def west(a):  # a[:, i - 1]
        return pltpu.roll(a, 1, 1)

    # the 128 lanes holding each buoy, and their still depth
    lanes = [slice(c // 128 * 128, c // 128 * 128 + 128) for c in buoys]
    still = [jnp.maximum(-b_ref[:, ls], 0.0) for ls in lanes]

    def step(k, carry):
        h, hu, kf, obs = carry
        u = velocity(h, hu)
        h_e = east(h)
        f_h, f_q, hs_l, hs_r = face_flux(h, h_e, b_ref[...], be_ref[...], bs_ref[...], u,
                                         east(u))
        f_l, f_r = well_balanced(f_q, h, h_e, hs_l, hs_r)
        p = 0.5 * G * h**2  # hydrostatic pressure on a wall
        div_h = jnp.where(east_wall, 0.0, f_h) - jnp.where(west_wall, 0.0, west(f_h))
        div_hu = jnp.where(east_wall, p, f_l) - jnp.where(west_wall, p, west(f_r))
        h, hu = update(h, hu, div_h, div_hu, lam)
        out = []
        for ls, h0, (mx, arr) in zip(lanes, still, obs):
            eta = h[:, ls] - h0
            out.append((jnp.maximum(mx, eta),
                        jnp.where((jnp.abs(eta) > thresh) & (arr < 0), kf, arr)))
        return h, hu, kf + 1.0, tuple(out)

    tile = (S, 128)
    init = tuple((jnp.full(tile, -jnp.inf, f32), jnp.full(tile, -1.0, f32)) for _ in buoys)
    h0 = h0_ref[...]
    h, hu, _, obs = jax.lax.fori_loop(
        0, steps, step, (h0, jnp.zeros_like(h0), jnp.zeros(tile, f32), init))
    for j, (mx, arr) in enumerate(obs):
        obs_ref[2 * j] = mx
        obs_ref[2 * j + 1] = arr
    if state_out:
        state_out[0][0] = h
        state_out[0][1] = hu


@functools.partial(jax.jit, static_argnames=(
    "n_cells", "strip", "steps", "lam", "buoys", "thresh", "keep_state", "interpret"))
def transect_kernel(h0, b, b_east, bstar, *, n_cells, strip, steps, lam, buoys, thresh,
                    keep_state=False, interpret=False):
    """Run `steps` steps of N chains on the padded transect.

    h0: [N, W] initial depth, N a multiple of `strip` (itself a multiple of
    8), W a multiple of 128 with cells from n_cells on zero; b, b_east,
    bstar: [strip, W], every row the bathymetry, its east neighbour's and
    the face bathymetry max(b, b_east) (any finite value where there is no
    face). Returns [2 * len(buoys), N, 128]: for each buoy the 128 lanes
    around it of the maximum elevation and of the first step above `thresh`
    (-1 if none), and, with `keep_state`, the final state [2, N, W]."""
    N, W = h0.shape
    if N % strip or strip % SUBLANES or W % 128:
        raise ValueError(f"strip {strip} must tile {N} chains in multiples of {SUBLANES}, "
                         f"and the width {W} be a multiple of 128")
    kern = functools.partial(_transect_kernel, n_cells=n_cells, steps=steps, lam=lam,
                             buoys=buoys, thresh=thresh)
    const = pl.BlockSpec((strip, W), lambda i: (0, 0))
    nobs = 2 * len(buoys)
    out_specs = [pl.BlockSpec((nobs, strip, 128), lambda i: (0, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((nobs, N, 128), jnp.float32)]
    if keep_state:
        out_specs.append(pl.BlockSpec((2, strip, W), lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((2, N, W), jnp.float32))
    out = pl.pallas_call(
        kern,
        grid=(N // strip,),
        in_specs=[pl.BlockSpec((strip, W), lambda i: (i, 0)), const, const, const],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(h0, b, b_east, bstar)
    return tuple(out) if keep_state else out[0]
