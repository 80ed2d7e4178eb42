"""Dispatcher of the 1-D transect's time-loop kernel: which path a platform
takes, and the padding, strip height, buoy read-out and per-device split of
the chains around the kernel."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.swe1d.swe1d import SUBLANES, transect_kernel
from repro.kernels.swe2d.ops import STRIP_ELEMS, padded_width


def padded_chains(n: int) -> int:
    """n rounded up to the 8 sublanes of a vector register."""
    return -(-n // SUBLANES) * SUBLANES


def strip_rows(n: int, w: int) -> int:
    """Chains of a strip on [n, w] (n a multiple of 8): the tallest multiple
    of 8 that tiles n and keeps a strip's array within STRIP_ELEMS (8 at
    least)."""
    fits = [s for s in range(SUBLANES, n + 1, SUBLANES) if n % s == 0 and s * w <= STRIP_ELEMS]
    return max(fits, default=SUBLANES)


def transect_path() -> str:
    """"pallas" where the time loop runs in the kernel, on a TPU; "xla" (the
    jnp scan) everywhere else. Any chain count and any transect fit: a
    strip's state is a few hundred KB of VMEM."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def bathymetry_rows(b) -> np.ndarray:
    """[3, W] of the bathymetry b [n_cells] (a host array): b, its east
    neighbour's and the face bathymetry max(b_L, b_R) to the east, each
    padded with zeros up to W = `padded_width`."""
    b = np.asarray(b, np.float32)
    rows = np.zeros((3, padded_width(len(b))), np.float32)
    rows[0, :len(b)] = b
    rows[1, :len(b) - 1] = b[1:]
    rows[2, :len(b) - 1] = np.maximum(b[:-1], b[1:])
    return rows


def _solve_rows(h0, bath, *, steps, lam, buoys, thresh, strip=None, keep_state=False):
    """The kernel on one device's chains: h0 [N, n_cells] padded to the
    kernel's tiles, the buoys' lanes read out. Returns obs [N, 2 * len(buoys)]
    (per buoy: max elevation, arrival step), and with `keep_state` also the
    final (h, hu), each [N, n_cells]."""
    N, n_cells = h0.shape
    n8, w = padded_chains(N), padded_width(n_cells)
    strip = strip or strip_rows(n8, w)
    h0 = jnp.pad(h0, ((0, n8 - N), (0, w - n_cells)))
    rows = [jnp.broadcast_to(bath[i], (strip, w)) for i in range(3)]
    out = transect_kernel(
        h0, *rows, n_cells=n_cells, strip=strip, steps=steps, lam=lam, buoys=tuple(buoys),
        thresh=thresh, keep_state=keep_state, interpret=jax.default_backend() != "tpu")
    tiles = out[0] if keep_state else out
    obs = jnp.stack([tiles[2 * j + k, :N, c % 128] for j, c in enumerate(buoys)
                     for k in range(2)], axis=1)
    if keep_state:
        return obs, (out[1][0, :N, :n_cells], out[1][1, :N, :n_cells])
    return obs


def _context_mesh():
    """The mesh the caller traces under (`with mesh:` or `jax.set_mesh`),
    empty where there is none."""
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.thread_resources.env.physical_mesh
    return mesh if not mesh.empty else mesh_lib.get_concrete_mesh()


@lru_cache(maxsize=None)
def _solver(steps: int, lam: float, buoys: tuple, thresh: float):
    """`_solve_rows` for one set of static arguments, as a function of
    (h0 [N, n_cells], bathymetry rows [3, W]) -> obs [N, 2 * len(buoys)]
    that folds a vmapped batch into the chain axis and, under a mesh that
    divides the chains, gives each device the chains of its own shard."""
    def solve(h0, bath):
        return _solve_rows(h0, bath, steps=steps, lam=lam, buoys=buoys, thresh=thresh)

    def sharded(h0, bath):
        # a Mosaic kernel is opaque to XLA's partitioner, which would gather
        # every chain onto every device and solve them all there
        mesh = _context_mesh()
        if mesh.empty or mesh.size == 1 or h0.shape[0] % mesh.size:
            return solve(h0, bath)
        chains = P(tuple(mesh.axis_names))
        return jax.shard_map(solve, mesh=mesh, in_specs=(chains, P()), out_specs=chains,
                             check_vma=False)(h0, bath)

    @jax.custom_batching.custom_vmap
    def batched(h0, bath):
        return sharded(h0, bath)

    @batched.def_vmap
    def _(axis_size, in_batched, h0, bath):
        # a batch of transects is more chains of one: the lockstep solve is
        # row-wise, so the batch axis folds into the chain axis (only h0 can
        # be batched: the bathymetry rows come from a host array)
        B, N, C = h0.shape
        return batched(h0.reshape(B * N, C), bath).reshape(B, N, -1), True

    return batched


def transect_solve(h0, b, *, steps, lam, buoys, thresh):
    """Run `steps` steps of the transect from the initial depth h0 [N,
    n_cells] (momentum at rest) over the bathymetry b [n_cells], a host
    array.

    Returns (mx, arr), each [len(buoys), N]: the largest elevation over the
    still depth at each buoy cell, and the first step at which its magnitude
    exceeded `thresh` (-1 if none). Under a mesh that divides the chains
    each device solves its own; off a TPU the kernel runs in interpret
    mode."""
    obs = _solver(steps, lam, tuple(buoys), thresh)(h0, jnp.asarray(bathymetry_rows(b)))
    return obs[..., 0::2].swapaxes(-1, -2), obs[..., 1::2].swapaxes(-1, -2)
