"""Plain references of the tsunami model's shallow-water steps.

`swe_step_ref` is one Rusanov / hydrostatic-reconstruction finite-volume
step over a ``[cells, batch]`` state block, the oracle of the 1-D step every
program of `repro.apps.tsunami` runs (`_transect_step`): the exact
arithmetic and OPERATION ORDER, with `jnp.sqrt` where the program's step has
its clamped-adjoint `_sqrt_safe` (equal in the primal). Hydrostatic
reconstruction against the interface bathymetry (Audusse et al.,
well-balanced with wetting & drying), Rusanov flux, well-balanced momentum
corrections, reflective walls, positivity/dry-cell limiter.
`tests/test_kernels.py` holds the two bit-equal under `jit`.
`swe_simulate_ref` runs it for one source at a time, every step's buoy
elevation kept: the per-source side `tests/test_batch_native.py` compares
the batch program against.

`swe2d_step_ref` / `swe2d_simulate_ref` are the plain reference of the 2-D
sea (`repro.apps.tsunami._solve_batch_2d`): one source at a time, written
out along each axis, with every step's buoy elevation kept. They share the
equations and not the code, so `tests/test_tsunami2d.py` compares the two
up to float32 reassociation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

G = 9.81
H_DRY = 0.05


def swe_step_ref(
    h: jax.Array,  # [C, N] water depth
    hu: jax.Array,  # [C, N] momentum
    b: jax.Array,  # [C, 1] bathymetry
    dt_dx: float,
    *,
    g: float = G,
    h_dry: float = H_DRY,
) -> tuple[jax.Array, jax.Array]:
    """One forward-Euler SWE step: (h, hu) -> (h_new, hu_new)."""
    bL, bR = b[:-1], b[1:]
    bstar = jnp.maximum(bL, bR)
    h4 = h**4
    # desingularized velocity (no division blow-up at the shoreline)
    u = jnp.sqrt(2.0) * h * hu / jnp.sqrt(h4 + jnp.maximum(h, h_dry) ** 4)
    hsL = jnp.maximum(h[:-1] + bL - bstar, 0.0)  # [C-1, N]
    hsR = jnp.maximum(h[1:] + bR - bstar, 0.0)
    uL, uR = u[:-1], u[1:]
    mL, mR = hsL * uL, hsR * uR  # interface mass fluxes
    a = jnp.maximum(
        jnp.abs(uL) + jnp.sqrt(g * hsL), jnp.abs(uR) + jnp.sqrt(g * hsR)
    )
    Fh = 0.5 * (mL + mR) - 0.5 * a * (hsR - hsL)
    Fq = 0.5 * ((mL * uL + 0.5 * g * hsL * hsL) + (mR * uR + 0.5 * g * hsR * hsR)) \
        - 0.5 * a * (mR - mL)
    # momentum flux + well-balanced interface correction, as seen from the
    # left cell (A) and from the right cell (B)
    A = Fq + 0.5 * g * (h[:-1] ** 2 - hsL**2)
    B = Fq + 0.5 * g * (h[1:] ** 2 - hsR**2)
    # flux divergence per cell; reflective walls (zero mass flux,
    # hydrostatic pressure g/2 h^2)
    div_h = jnp.concatenate([Fh[:1], Fh[1:] - Fh[:-1], -Fh[-1:]], 0)
    pL = 0.5 * g * h[:1] ** 2
    pR = 0.5 * g * h[-1:] ** 2
    div_hu = jnp.concatenate([A[:1] - pL, A[1:] - B[:-1], pR - B[-1:]], 0)
    h_new = jnp.maximum(h - dt_dx * div_h, 0.0)
    hu_new = jnp.where(h_new > h_dry, hu - dt_dx * div_hu, 0.0)
    return h_new, hu_new


def swe_simulate_ref(
    h: jax.Array,  # [C] initial depth of one source
    b: jax.Array,  # [C]
    dt_dx: float,
    n_steps: int,
    rows: tuple,  # cells of the buoys
    *,
    g: float = G,
    h_dry: float = H_DRY,
) -> jax.Array:
    """[n_steps, len(rows)] surface elevation at the buoys after each step,
    starting at rest from depth `h`: `swe_step_ref` in a plain loop over
    time steps, one source at a time, every step's elevation kept (the
    arrival and the maximum are left to the caller)."""
    h_still = jnp.maximum(-b, 0.0)
    b = b[:, None]

    def step(state, _):
        state = swe_step_ref(*state, b, dt_dx, g=g, h_dry=h_dry)
        return state, jnp.stack([state[0][r, 0] - h_still[r] for r in rows])

    h = h[:, None]
    _, etas = jax.lax.scan(step, (h, jnp.zeros_like(h)), None, length=n_steps)
    return etas


def swe2d_step_ref(
    h: jax.Array,  # [ny, nx] water depth of one source
    hu: jax.Array,  # [ny, nx] momentum along x
    hv: jax.Array,  # [ny, nx] momentum along y
    b: jax.Array,  # [ny, nx] bathymetry
    dt_dx: float,
    dt_dy: float,
    *,
    g: float = G,
    h_dry: float = H_DRY,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One forward-Euler step of the 2-D shallow-water equations for ONE
    source, written out along x and then along y: Rusanov fluxes with
    hydrostatic reconstruction against the face bathymetry max(b_L, b_R),
    the well-balanced pressure correction of the normal momentum, reflective
    walls (no mass or tangential flux, hydrostatic pressure g/2 h^2) and the
    positivity / dry-cell limiter of the 1-D step.

    Departures from the equations, shared with the program: the velocity
    is desingularised, u = sqrt(2) h hu / sqrt(h^4 + max(h, h_dry)^4), so
    that it stays finite at the shoreline; depth is clipped at zero, and
    momentum zeroed where the new depth is under `h_dry`; the two axes are
    updated at once (unsplit), first order in space and time."""
    inv = jnp.sqrt(2.0) * h / jnp.sqrt(h**4 + jnp.maximum(h, h_dry) ** 4)
    u, v = inv * hu, inv * hv

    # x faces, between columns j and j + 1: u is normal, v tangential
    bs = jnp.maximum(b[:, :-1], b[:, 1:])
    hl = jnp.maximum(h[:, :-1] + b[:, :-1] - bs, 0.0)
    hr = jnp.maximum(h[:, 1:] + b[:, 1:] - bs, 0.0)
    ul, ur, vl, vr = u[:, :-1], u[:, 1:], v[:, :-1], v[:, 1:]
    a = jnp.maximum(jnp.abs(ul) + jnp.sqrt(g * hl), jnp.abs(ur) + jnp.sqrt(g * hr))
    fh = 0.5 * (hl * ul + hr * ur) - 0.5 * a * (hr - hl)
    fu = 0.5 * ((hl * ul * ul + 0.5 * g * hl * hl) + (hr * ur * ur + 0.5 * g * hr * hr)) \
        - 0.5 * a * (hr * ur - hl * ul)
    fv = 0.5 * (hl * ul * vl + hr * ur * vr) - 0.5 * a * (hr * vr - hl * vl)
    fu_l = fu + 0.5 * g * (h[:, :-1] ** 2 - hl**2)  # as the left cell sees it
    fu_r = fu + 0.5 * g * (h[:, 1:] ** 2 - hr**2)  # as the right cell sees it
    dhx = jnp.concatenate([fh[:, :1], fh[:, 1:] - fh[:, :-1], -fh[:, -1:]], 1)
    dux = jnp.concatenate([fu_l[:, :1] - 0.5 * g * h[:, :1] ** 2, fu_l[:, 1:] - fu_r[:, :-1],
                           0.5 * g * h[:, -1:] ** 2 - fu_r[:, -1:]], 1)
    dvx = jnp.concatenate([fv[:, :1], fv[:, 1:] - fv[:, :-1], -fv[:, -1:]], 1)

    # y faces, between rows i and i + 1: v is normal, u tangential
    bs = jnp.maximum(b[:-1], b[1:])
    hl = jnp.maximum(h[:-1] + b[:-1] - bs, 0.0)
    hr = jnp.maximum(h[1:] + b[1:] - bs, 0.0)
    vl, vr, ul, ur = v[:-1], v[1:], u[:-1], u[1:]
    a = jnp.maximum(jnp.abs(vl) + jnp.sqrt(g * hl), jnp.abs(vr) + jnp.sqrt(g * hr))
    fh = 0.5 * (hl * vl + hr * vr) - 0.5 * a * (hr - hl)
    fv = 0.5 * ((hl * vl * vl + 0.5 * g * hl * hl) + (hr * vr * vr + 0.5 * g * hr * hr)) \
        - 0.5 * a * (hr * vr - hl * vl)
    fu = 0.5 * (hl * vl * ul + hr * vr * ur) - 0.5 * a * (hr * ur - hl * ul)
    fv_l = fv + 0.5 * g * (h[:-1] ** 2 - hl**2)
    fv_r = fv + 0.5 * g * (h[1:] ** 2 - hr**2)
    dhy = jnp.concatenate([fh[:1], fh[1:] - fh[:-1], -fh[-1:]], 0)
    dvy = jnp.concatenate([fv_l[:1] - 0.5 * g * h[:1] ** 2, fv_l[1:] - fv_r[:-1],
                           0.5 * g * h[-1:] ** 2 - fv_r[-1:]], 0)
    duy = jnp.concatenate([fu[:1], fu[1:] - fu[:-1], -fu[-1:]], 0)

    h_new = jnp.maximum(h - dt_dx * dhx - dt_dy * dhy, 0.0)
    wet = h_new > h_dry
    hu_new = jnp.where(wet, hu - dt_dx * dux - dt_dy * duy, 0.0)
    hv_new = jnp.where(wet, hv - dt_dx * dvx - dt_dy * dvy, 0.0)
    return h_new, hu_new, hv_new


def swe2d_simulate_ref(
    h: jax.Array,  # [ny, nx] initial depth of one source
    b: jax.Array,  # [ny, nx]
    dt_dx: float,
    dt_dy: float,
    n_steps: int,
    sites: tuple,  # ((row, column), ...) of the buoys
    *,
    g: float = G,
    h_dry: float = H_DRY,
) -> jax.Array:
    """[n_steps, len(sites)] surface elevation at the buoys after each step,
    starting at rest from depth `h`: `swe2d_step_ref` in a plain loop over
    time steps, one source at a time, every step's elevation kept (the
    arrival and the maximum are left to the caller)."""
    h_still = jnp.maximum(-b, 0.0)

    def step(state, _):
        state = swe2d_step_ref(*state, b, dt_dx, dt_dy, g=g, h_dry=h_dry)
        return state, jnp.stack([state[0][r, c] - h_still[r, c] for r, c in sites])

    zero = jnp.zeros_like(h)
    _, etas = jax.lax.scan(step, (h, zero, zero), None, length=n_steps)
    return etas
