"""The 2-D sea's whole time loop in one Pallas TPU kernel.

The physics of one step is written once here: the desingularised
velocities, the Rusanov flux with hydrostatic reconstruction across a face,
and the update with its positivity / dry-cell limiter. The jnp step of
`repro.apps.tsunami` (`_sea_step`) and the kernel below both call them on
their own operands, in the same order of operations.

The kernel's grid runs over the lanes (sources) of a chunk. For one lane it
loads the initial depth once, keeps (h, hu, hv) in VMEM scratch for every
time step, ping-ponging between two buffers, and writes only the buoys'
maximum elevation and arrival step. A step sweeps the sea in strips of
`strip` rows: x neighbours are lane rolls of a row, masked at the walls
(column nx - 1 is the east wall; the columns padded up to a multiple of 128
are inert); y neighbours come from the strip plus the 8 rows under it, and
the faces above a strip's first row are carried over from the strip before.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

G = 9.81
H_DRY = 0.05  # wetting/drying threshold [m]
SQRT2 = float(np.sqrt(np.float32(2.0)))
#: rows a strip reads under itself: one (8, 128) tile of sublanes
HALO = 8


def velocities(h, hu, hv):
    """Desingularised velocities (u, v): finite at the shoreline."""
    inv = SQRT2 * h / jnp.sqrt(h**4 + jnp.maximum(h, H_DRY) ** 4)
    return inv * hu, inv * hv


def face_flux(hL, hR, bL, bR, bstar, unL, unR, utL, utR):
    """Rusanov flux across faces whose normal points from cell L to cell R,
    with hydrostatic reconstruction (Audusse et al.) against the face
    bathymetry `bstar`; `un` is the velocity along the normal, `ut` across
    it. Returns the mass flux, the normal momentum flux with its
    well-balanced pressure correction as cell L and as cell R see it, and
    the tangential momentum flux. Written once for both axes."""
    hsL = jnp.maximum(hL + bL - bstar, 0.0)
    hsR = jnp.maximum(hR + bR - bstar, 0.0)
    mL, mR = hsL * unL, hsR * unR
    a = jnp.maximum(jnp.abs(unL) + jnp.sqrt(G * hsL), jnp.abs(unR) + jnp.sqrt(G * hsR))
    f_h = 0.5 * (mL + mR) - 0.5 * a * (hsR - hsL)
    f_n = 0.5 * ((mL * unL + 0.5 * G * hsL * hsL) + (mR * unR + 0.5 * G * hsR * hsR)) \
        - 0.5 * a * (mR - mL)
    f_t = 0.5 * (mL * utL + mR * utR) - 0.5 * a * (hsR * utR - hsL * utL)
    return (f_h, f_n + 0.5 * G * (hL**2 - hsL**2), f_n + 0.5 * G * (hR**2 - hsR**2), f_t)


def update(h, hu, hv, div_x, div_y, lam_x, lam_y):
    """The unsplit update from the flux differences (dh, dhu, dhv) along x
    and along y: depth clipped at zero, momentum zeroed where the new depth
    is under H_DRY."""
    h_new = jnp.maximum(h - lam_x * div_x[0] - lam_y * div_y[0], 0.0)
    wet = h_new > H_DRY
    hu_new = jnp.where(wet, hu - lam_x * div_x[1] - lam_y * div_y[1], 0.0)
    hv_new = jnp.where(wet, hv - lam_x * div_x[2] - lam_y * div_y[2], 0.0)
    return h_new, hu_new, hv_new


def _sea_kernel(h0_ref, b_ref, bsx_ref, bsy_ref, obs_ref, *rest, nx, ny, strip, steps,
                lam_x, lam_y, buoys, thresh):
    """One lane: every step of the sea in VMEM, then the buoys' tiles of
    [max elevation, arrival step] into `obs_ref` (and the final state into
    the optional state output)."""
    *state_out, st = rest  # st: [2 buffers, 3 fields, ny + HALO, W]
    W = st.shape[-1]
    S = strip
    f32 = jnp.float32

    for buf in range(2):
        for f in range(3):
            st[buf, f, ny:, :] = jnp.zeros((HALO, W), f32)
    st[0, 0, :ny, :] = h0_ref[...]
    st[0, 1, :ny, :] = jnp.zeros((ny, W), f32)
    st[0, 2, :ny, :] = jnp.zeros((ny, W), f32)

    col = jax.lax.broadcasted_iota(jnp.int32, (S, W), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (S, W), 0)
    east_wall = col >= nx - 1
    west_wall = (col == 0) | (col >= nx)

    def east(a):  # a[:, j + 1]
        return pltpu.roll(a, W - 1, 1)

    def west(a):  # a[:, j - 1]
        return pltpu.roll(a, 1, 1)

    def south(a):  # a[i + 1] of an (S + HALO)-row block, for its first S rows
        return pltpu.roll(a, S + HALO - 1, 0)[:S]

    def north(last, a):  # a[i - 1], the row before the first taken from `last`
        return pltpu.roll(jnp.concatenate([last, a], 0), 1, 0)[HALO:]

    def sweep(src, dst):
        def body(s, above):
            r0 = pl.multiple_of(s * S, HALO)
            block = pl.ds(r0, S + HALO)
            h_b, hu_b, hv_b = (st[src, f, block, :] for f in range(3))
            b_b = b_ref[block, :]
            u_b, v_b = velocities(h_b, hu_b, hv_b)
            h, hu, hv, b, u, v = (x[:S] for x in (h_b, hu_b, hv_b, b_b, u_b, v_b))
            p = 0.5 * G * h**2  # hydrostatic pressure on a wall

            # x faces: from each cell to its east neighbour
            f_h, f_l, f_r, f_t = face_flux(h, east(h), b, east(b), bsx_ref[pl.ds(r0, S), :],
                                           u, east(u), v, east(v))
            div_x = (jnp.where(east_wall, 0.0, f_h) - jnp.where(west_wall, 0.0, west(f_h)),
                     jnp.where(east_wall, p, f_l) - jnp.where(west_wall, p, west(f_r)),
                     jnp.where(east_wall, 0.0, f_t) - jnp.where(west_wall, 0.0, west(f_t)))

            # y faces: from each cell to the row under it; v is the normal
            g_h, g_l, g_r, g_t = face_flux(h, south(h_b), b, south(b_b), bsy_ref[pl.ds(r0, S), :],
                                           v, south(v_b), u, south(u_b))
            i = r0 + row
            s_wall, n_wall = i == ny - 1, i == 0
            a_h, a_r, a_t = above
            div_y = (jnp.where(s_wall, 0.0, g_h) - jnp.where(n_wall, 0.0, north(a_h, g_h)),
                     jnp.where(s_wall, 0.0, g_t) - jnp.where(n_wall, 0.0, north(a_t, g_t)),
                     jnp.where(s_wall, p, g_l) - jnp.where(n_wall, p, north(a_r, g_r)))

            for f, x in enumerate(update(h, hu, hv, div_x, div_y, lam_x, lam_y)):
                st[dst, f, pl.ds(r0, S), :] = x
            return g_h[S - HALO:], g_r[S - HALO:], g_t[S - HALO:]

        edge = jnp.zeros((HALO, W), f32)
        jax.lax.fori_loop(0, ny // S, body, (edge, edge, edge))

    # the (8, 128) tile holding each buoy, and its still depth
    tiles = [(pl.ds(r // 8 * 8, 8), pl.ds(c // 128 * 128, 128)) for r, c in buoys]
    still = [jnp.maximum(-b_ref[rs, cs], 0.0) for rs, cs in tiles]

    def step(k, carry):
        kf, obs = carry
        src = jax.lax.rem(k, 2)
        sweep(src, 1 - src)
        out = []
        for (rs, cs), h0, (mx, arr) in zip(tiles, still, obs):
            eta = st[1 - src, 0, rs, cs] - h0
            out.append((jnp.maximum(mx, eta),
                        jnp.where((jnp.abs(eta) > thresh) & (arr < 0), kf, arr)))
        return kf + 1.0, tuple(out)

    tile = (8, 128)
    init = tuple((jnp.full(tile, -jnp.inf, f32), jnp.full(tile, -1.0, f32)) for _ in buoys)
    _, obs = jax.lax.fori_loop(0, steps, step, (jnp.zeros(tile, f32), init))
    for j, (mx, arr) in enumerate(obs):
        obs_ref[2 * j] = mx
        obs_ref[2 * j + 1] = arr
    if state_out:
        for f in range(3):
            state_out[0][f] = st[steps % 2, f, :ny, :]


def vmem_bytes(ny: int, w: int, strip: int) -> int:
    """VMEM the kernel asks for on a [ny, w] (padded) sea: the ping-pong
    state, the double-buffered bathymetry and initial depth, and room for
    some 64 temporaries of a strip."""
    rows = ny + HALO
    state = 2 * 3 * rows * w
    inputs = 2 * (3 * rows * w + ny * w)
    temporaries = 64 * (strip + HALO) * w
    return 4 * (state + inputs + temporaries) + (4 << 20)


@functools.partial(jax.jit, static_argnames=(
    "nx", "strip", "steps", "lam_x", "lam_y", "buoys", "thresh", "keep_state", "interpret"))
def sea_kernel(h0, b, bstar_x, bstar_y, *, nx, strip, steps, lam_x, lam_y, buoys, thresh,
               keep_state=False, interpret=False):
    """Run `steps` steps of N lanes on the padded sea.

    h0: [N, ny, W] initial depth, W a multiple of 128 with columns from nx
    on zero; b, bstar_x, bstar_y: [ny + HALO, W] bathymetry and face
    bathymetry to the east and to the south (any finite value where there
    is no face). Returns [N, 2 * len(buoys), 8, 128]: for each buoy the
    (8, 128) tile around it of the maximum elevation and of the first step
    above `thresh` (-1 if none), and, with `keep_state`, the final state
    [N, 3, ny, W]."""
    N, ny, W = h0.shape
    if ny % strip or strip % HALO or W % 128:
        raise ValueError(f"strip {strip} must tile {ny} rows in multiples of {HALO}, "
                         f"and the width {W} be a multiple of 128")
    kern = functools.partial(_sea_kernel, nx=nx, ny=ny, strip=strip, steps=steps,
                             lam_x=lam_x, lam_y=lam_y, buoys=buoys, thresh=thresh)
    const = pl.BlockSpec((ny + HALO, W), lambda n: (0, 0))
    nobs = 2 * len(buoys)
    out_specs = [pl.BlockSpec((None, nobs, 8, 128), lambda n: (n, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, nobs, 8, 128), jnp.float32)]
    if keep_state:
        out_specs.append(pl.BlockSpec((None, 3, ny, W), lambda n: (n, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((N, 3, ny, W), jnp.float32))
    out = pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=[pl.BlockSpec((None, ny, W), lambda n: (n, 0, 0)), const, const, const],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, 3, ny + HALO, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(ny, W, strip)),
        interpret=interpret,
    )(h0, b, bstar_x, bstar_y)
    return tuple(out) if keep_state else out[0]
