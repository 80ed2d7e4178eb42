"""Dispatcher of the 2-D sea's time-loop kernel: which path a grid takes,
and the padding, strip height and buoy read-out around the kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.swe2d.swe2d import HALO, sea_kernel, vmem_bytes

#: elements of one array of a strip: 16 (8, 128) tiles
STRIP_ELEMS = 16 * 8 * 128


def padded_width(nx: int) -> int:
    """nx rounded up to the 128 lanes of a vector register."""
    return -(-nx // 128) * 128


def strip_rows(ny: int, w: int) -> int:
    """Rows of a strip on a [ny, w] sea: the tallest multiple of 8 that
    tiles ny and keeps a strip's array within STRIP_ELEMS (8 at least)."""
    fits = [s for s in range(HALO, ny + 1, HALO) if ny % s == 0 and s * w <= STRIP_ELEMS]
    return max(fits, default=HALO)


def sea_path(ny: int, nx: int) -> str:
    """"pallas" where the time loop runs in the kernel: on a TPU, for a sea
    whose rows tile by 8 and whose state fits half the core's VMEM; "xla"
    (the jnp scan) everywhere else."""
    if jax.default_backend() != "tpu" or ny % HALO:
        return "xla"
    from jax.experimental.pallas import tpu as pltpu

    w = padded_width(nx)
    fits = vmem_bytes(ny, w, strip_rows(ny, w)) <= pltpu.get_tpu_info().vmem_capacity_bytes // 2
    return "pallas" if fits else "xla"


def padded(h0, b):
    """The kernel's operands: the initial depth h0 [N, ny, nx] with zero
    columns up to the padded width W, and the bathymetry b [ny, nx] (a host
    array) with the face bathymetry to the east and to the south, each
    [ny + HALO, W] with zeros where there is no cell or no face."""
    _, ny, nx = h0.shape
    w = padded_width(nx)
    b = np.asarray(b, np.float32)
    bath = np.zeros((3, ny + HALO, w), np.float32)
    bath[0, :ny, :nx] = b
    bath[1, :ny, :nx - 1] = np.maximum(b[:, :-1], b[:, 1:])
    bath[2, :ny - 1, :nx] = np.maximum(b[:-1], b[1:])
    return (jnp.pad(h0, ((0, 0), (0, 0), (0, w - nx))), *(jnp.asarray(x) for x in bath))


def sea_solve(h0, b, *, steps, lam_x, lam_y, buoys, thresh, strip=None, keep_state=False):
    """Run `steps` steps of the sea from the initial depth h0 [N, ny, nx]
    (momentum at rest) over the bathymetry b [ny, nx], a host array.

    Returns (mx, arr), each [len(buoys), N]: the largest elevation over the
    still depth at each buoy (row, column), and the first step at which its
    magnitude exceeded `thresh` (-1 if none); with `keep_state`, also the
    final (h, hu, hv), each [N, ny, nx]. The strip height comes from the
    shape; off a TPU the kernel runs in interpret mode."""
    _, ny, nx = h0.shape
    h0, *bath = padded(h0, b)
    out = sea_kernel(
        h0, *bath, nx=nx, strip=strip or strip_rows(ny, h0.shape[-1]), steps=steps,
        lam_x=lam_x, lam_y=lam_y, buoys=tuple(buoys), thresh=thresh, keep_state=keep_state,
        interpret=jax.default_backend() != "tpu")
    tiles = out[0] if keep_state else out
    obs = jnp.stack([tiles[:, 2 * j:2 * j + 2, r % 8, c % 128] for j, (r, c) in enumerate(buoys)])
    mx, arr = obs[..., 0], obs[..., 1]
    if keep_state:
        return mx, arr, tuple(out[1][:, f, :, :nx] for f in range(3))
    return mx, arr
