"""Plain reference for the 2-D tsunami configuration: the shallow-water
equations on a 400 km x 400 km sea that stands in for arXiv:2304.14087
§4.3's Tohoku grid, written from its equations.

Rusanov finite volumes along x and along y with hydrostatic reconstruction
(Audusse et al.) for the bathymetry source, a desingularised velocity for
wetting and drying, and reflective walls; the two axes update the state at
once. The source is a Gaussian hump of the configuration's amplitude and
width at theta = (x0, y0) km. The observables are, at each buoy, the first
time |eta| exceeds the arrival threshold and the largest eta. Every size
comes from the configuration's JSON file; nothing here is taken from the
program.

`observables_block` solves a block of sources in one vmapped program, in the
dtype given: float32 is the reference, bfloat16 the lower-precision control
that the comparison has to reject.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def bathymetry(cfg: dict, nx: int, ny: int, smoothed: bool) -> np.ndarray:
    """Seafloor elevation b(x, y) [m] at the cell centres, [ny, nx]
    (float64, host): the transect's profile in the distance from a coast
    that bends by `bend_km` sin(2 pi y / L) along y."""
    bt, L = cfg["bathymetry"], cfg["domain_m"]
    xk = (np.arange(nx) + 0.5) * (L / nx) / 1e3
    yk = (np.arange(ny) + 0.5) * (L / ny) / 1e3
    xk = xk[None, :] - bt["bend_km"] * np.sin(2 * np.pi * yk[:, None] / (L / 1e3))
    shelf = bt["deep_m"] + (bt["shelf_m"] - bt["deep_m"]) * _sigmoid(
        (xk - bt["shelf_km"]) / bt["shelf_width_km"])
    beach = (bt["beach_m"] - bt["shelf_m"]) * _sigmoid(
        (xk - bt["beach_km"]) / bt["beach_width_km"])
    b = shelf + beach
    if not smoothed:
        r = bt["ridges"]
        b = b + r["amplitude_m"] * np.sin(xk / r["wavelength_km"]) * _sigmoid(
            (xk - r["from_km"]) / r["ramp_km"]) * _sigmoid((r["to_km"] - xk) / r["ramp_km"])
    return b


def grid(cfg: dict, level: int) -> dict:
    """Sizes of one level: cells, spacings, time step, steps, buoy cells."""
    lv = cfg["levels"][level]
    nx, ny = lv["nx"], lv["ny"]
    dx, dy = cfg["domain_m"] / nx, cfg["domain_m"] / ny
    dt = cfg["cfl"] * min(dx, dy) / math.sqrt(cfg["g"] * cfg["c_max_depth_m"])
    return {
        "nx": nx, "ny": ny, "smoothed": lv["smoothed"], "dx": dx, "dy": dy, "dt": dt,
        "n_steps": int(cfg["t_end_s"] / dt),
        "buoys": [(int(y * 1e3 / dy), int(x * 1e3 / dx)) for x, y in cfg["buoys_km"]],
    }


def make_solver(cfg: dict, level: int, dtype):
    """(step, solve) of one level in `dtype` throughout: `step` advances the
    carry (h, hu, hv, peak, arrival) of one source by one time step, and
    `solve` maps theta [2] to the observables [4]."""
    gr = grid(cfg, level)
    nx, ny, dt = gr["nx"], gr["ny"], gr["dt"]

    def c(v):
        return jnp.asarray(v, dtype)

    g, h_dry = c(cfg["g"]), c(cfg["h_dry_m"])
    zero, half = c(0.0), c(0.5)
    lam_x, lam_y = c(dt / gr["dx"]), c(dt / gr["dy"])
    b = c(bathymetry(cfg, nx, ny, gr["smoothed"]))
    h_still = jnp.maximum(-b, zero)
    x = c((np.arange(nx) + 0.5) * gr["dx"])
    y = c((np.arange(ny) + 0.5) * gr["dy"])
    buoys = gr["buoys"]

    def fluxes(hl, hr, ul, ur, vl, vr):
        """Rusanov flux across faces with normal velocity u and tangential
        velocity v, from the reconstructed depths on each side."""
        ml, mr = hl * ul, hr * ur
        speed = jnp.maximum(jnp.abs(ul) + jnp.sqrt(g * hl), jnp.abs(ur) + jnp.sqrt(g * hr))
        damp = half * speed
        f_h = half * (ml + mr) - damp * (hr - hl)
        f_u = half * ((ml * ul + half * g * hl * hl) + (mr * ur + half * g * hr * hr)) \
            - damp * (mr - ml)
        f_v = half * (ml * vl + mr * vr) - damp * (hr * vr - hl * vl)
        return f_h, f_u, f_v

    def differences(f_h, f_left, f_right, f_t, h, axis):
        """Flux out minus flux in per cell along `axis` (0: y, 1: x);
        reflective walls: no mass or tangential flux, hydrostatic pressure."""
        def part(a, lo, hi):
            return jax.lax.slice_in_dim(a, lo, hi, axis=axis)

        n = h.shape[axis]
        div_h = jnp.concatenate([part(f_h, 0, 1), part(f_h, 1, n - 1) - part(f_h, 0, n - 2),
                                 -part(f_h, n - 2, n - 1)], axis)
        div_n = jnp.concatenate([
            part(f_left, 0, 1) - half * g * part(h, 0, 1) ** 2,
            part(f_left, 1, n - 1) - part(f_right, 0, n - 2),
            half * g * part(h, n - 1, n) ** 2 - part(f_right, n - 2, n - 1),
        ], axis)
        div_t = jnp.concatenate([part(f_t, 0, 1), part(f_t, 1, n - 1) - part(f_t, 0, n - 2),
                                 -part(f_t, n - 2, n - 1)], axis)
        return div_h, div_n, div_t

    def lower(a, axis):
        return jax.lax.slice_in_dim(a, 0, a.shape[axis] - 1, axis=axis)

    def upper(a, axis):
        return jax.lax.slice_in_dim(a, 1, a.shape[axis], axis=axis)

    # the face bathymetry of the hydrostatic reconstruction, fixed in time
    b_star = {axis: jnp.maximum(lower(b, axis), upper(b, axis)) for axis in (0, 1)}

    def along(h, un, ut, axis):
        """The three flux differences of the faces normal to `axis`."""
        def lo(a):
            return lower(a, axis)

        def hi(a):
            return upper(a, axis)

        hl = jnp.maximum(lo(h) + lo(b) - b_star[axis], zero)
        hr = jnp.maximum(hi(h) + hi(b) - b_star[axis], zero)
        f_h, f_n, f_t = fluxes(hl, hr, lo(un), hi(un), lo(ut), hi(ut))
        # well-balanced pressure corrections, seen from each side
        f_left = f_n + half * g * (lo(h) ** 2 - hl**2)
        f_right = f_n + half * g * (hi(h) ** 2 - hr**2)
        return differences(f_h, f_left, f_right, f_t, h, axis)

    def step(carry, i):
        h, hu, hv, peak, arrival = carry
        scale = c(math.sqrt(2.0)) * h / jnp.sqrt(h**4 + jnp.maximum(h, h_dry) ** 4)
        u, v = scale * hu, scale * hv
        dh_x, du_x, dv_x = along(h, u, v, 1)
        dh_y, dv_y, du_y = along(h, v, u, 0)
        h = jnp.maximum(h - lam_x * dh_x - lam_y * dh_y, zero)
        wet = h > h_dry
        hu = jnp.where(wet, hu - lam_x * du_x - lam_y * du_y, zero)
        hv = jnp.where(wet, hv - lam_x * dv_x - lam_y * dv_y, zero)
        eta = jnp.stack([h[r, q] - h_still[r, q] for r, q in buoys])
        peak = jnp.maximum(peak, eta)
        arrival = jnp.where((jnp.abs(eta) > cfg["arrival_threshold_m"]) & (arrival < 0),
                            i, arrival)
        return (h, hu, hv, peak, arrival), None

    def solve(theta):
        theta = jnp.asarray(theta, dtype)
        width = c(cfg["source_width_km"] * 1e3)
        r2 = (x[None, :] - theta[0] * c(1e3)) ** 2 + (y[:, None] - theta[1] * c(1e3)) ** 2
        eta0 = c(cfg["source_amplitude_m"]) * jnp.exp(-r2 / width**2)
        h = jnp.maximum(h_still + eta0 * (h_still > h_dry), zero)
        init = (h, jnp.zeros_like(h), jnp.zeros_like(h),
                jnp.full((len(buoys),), -jnp.inf, dtype), jnp.full((len(buoys),), -1, jnp.int32))
        (_, _, _, peak, arrival), _ = jax.lax.scan(
            step, init, jnp.arange(gr["n_steps"], dtype=jnp.int32))
        minutes = jnp.where(arrival >= 0, arrival.astype(jnp.float32) * (dt / 60.0),
                            cfg["t_end_s"] / 60.0)
        return jnp.stack([minutes, peak.astype(jnp.float32)], axis=1).reshape(-1)

    return step, solve


@lru_cache(maxsize=None)
def _programs(cfg_json: str, level: int, dtype_name: str):
    _, solve = make_solver(json.loads(cfg_json), level, jnp.dtype(dtype_name))
    return jax.jit(jax.vmap(solve))


def observables_block(cfg: dict, level: int, thetas, dtype="float32") -> np.ndarray:
    """[N, 2] sources -> [N, 4] rows [arrival_1 (min), height_1 (m),
    arrival_2, height_2], in one program."""
    fwd = _programs(json.dumps(cfg, sort_keys=True), level, str(jnp.dtype(dtype)))
    return np.asarray(fwd(jnp.asarray(np.asarray(thetas, np.float64), dtype)), np.float64)
