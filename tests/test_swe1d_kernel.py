"""The 1-D transect's time-loop kernel (`repro.kernels.swe1d`, interpret
mode) against the jnp scan it shares its physics with: the state after a
few steps at both levels' cell counts, the observables, a lake at rest,
conservation of mass, inert padding, the path each platform takes, the
derivative programs (the scan's, bit for bit), and a vmapped or sharded
call."""
import json
import os
import subprocess
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import tsunami as ts
from repro.apps.tsunami import TsunamiModel
from repro.core import tracing
from repro.kernels.swe1d import ops, transect_kernel, transect_path

#: one unit in the last place of float32 at the deepest still water (4 km):
#: the resolution of the elevation, which is depth minus still depth
DEPTH_ULP = float(np.spacing(np.float32(4000.0)))
LEVELS = {0: (512, True), 1: (2048, False)}
ROOT = Path(__file__).resolve().parents[1]


def _thetas(n=3, seed=7):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack([rng.uniform(40, 140, n), rng.uniform(0.8, 3.5, n)], 1),
                       jnp.float32)


def _lam(n_cells, smoothed):
    return ts.Grid(n_cells, smoothed=smoothed).dt / (ts.L_DOMAIN / n_cells)


def _kernel_run(h0, b, steps, strip=None):
    """(h, hu), each [N, cells], after `steps` kernel steps from the depth
    h0 [N, cells] at rest over the bathymetry b [cells]."""
    n_cells = h0.shape[1]
    _, state = ops._solve_rows(h0, jnp.asarray(ops.bathymetry_rows(b)), steps=steps,
                               lam=_lam(n_cells, True), buoys=ts._buoy_rows(n_cells),
                               thresh=ts.ARRIVAL_THRESH, strip=strip, keep_state=True)
    return state


def _momentum_rounding(lam, steps):
    """How far float32 reassociation can move momentum in `steps` steps: a
    step adds dt/dx times the rounding of the hydrostatic pressure g/2 h^2
    (a unit in the last place at 4 km depth)."""
    return steps * lam * float(np.spacing(np.float32(0.5 * ts.G * 4000.0**2)))


@pytest.mark.parametrize("strip", [8, None], ids=["strip8", "strip_of_shape"])
@pytest.mark.parametrize("level", [0, 1])
def test_kernel_state_matches_the_scan_step(level, strip):
    n_cells, smoothed = LEVELS[level]
    steps, lam = 30, _lam(n_cells, smoothed)
    b = ts._bathymetry_cached(n_cells, smoothed)
    h = ts._transect_source(_thetas(), n_cells, smoothed)  # [cells, N]
    step = jax.jit(ts._transect_step(jnp.asarray(b)[:, None], lam))
    want = h, jnp.zeros_like(h)
    for _ in range(steps):
        want = step(*want)
    n8 = ops.padded_chains(3)
    _, got = ops._solve_rows(h.T, jnp.asarray(ops.bathymetry_rows(b)), steps=steps, lam=lam,
                             buoys=ts._buoy_rows(n_cells),
                             thresh=ts.ARRIVAL_THRESH, strip=strip or ops.strip_rows(n8, n_cells),
                             keep_state=True)
    # the same operations in another fusion: float32 reassociation only
    np.testing.assert_allclose(got[0], want[0].T, rtol=0, atol=4 * DEPTH_ULP)
    np.testing.assert_allclose(got[1], want[1].T, rtol=0, atol=_momentum_rounding(lam, steps))
    assert float(jnp.max(jnp.abs(want[1]))) > 1.0  # the water moved


@pytest.mark.parametrize("n", [4, 16, 128])
def test_kernel_observables_match_the_scan(n):
    thetas = _thetas(n, seed=n)
    solve = jax.jit(ts._transect_observables, static_argnums=(1, 2, 3))
    got = np.asarray(solve(thetas, 512, True, "pallas"))
    want = np.asarray(solve(thetas, 512, True, "xla"))
    # the tolerances of the batch against the per-source reference
    # (test_batch_native.py): float32 reassociation over ~2e3 steps moves
    # an arrival by a step and a height by well under 2%
    np.testing.assert_allclose(got[:, ::2], want[:, ::2], atol=0.05)
    np.testing.assert_allclose(got[:, 1::2], want[:, 1::2], rtol=2e-2)
    assert np.all(want[:, ::2] < ts.T_END / 60)  # the waves reach the buoys


def test_kernel_keeps_a_lake_at_rest():
    n_cells, smoothed = LEVELS[0]
    b = ts._bathymetry_cached(n_cells, smoothed)
    still = jnp.asarray(np.maximum(-b, 0.0))[None]
    h, hu = _kernel_run(still, b, ts.Grid(n_cells).steps)
    # the hydrostatic pressure g/2 h^2 (about 8e7 at 4 km) balances across
    # each face up to its float32 rounding (about 8); over 2,224 steps that
    # moves the depth by a few units in the last place and the momentum by a
    # few units, in the scan too (3 ulps and 2.0)
    assert float(jnp.max(jnp.abs(h - still))) <= 4 * DEPTH_ULP
    assert float(jnp.max(jnp.abs(hu))) < 4.0


def test_kernel_conserves_mass_between_the_walls():
    n_cells, smoothed = LEVELS[0]
    b = ts._bathymetry_cached(n_cells, smoothed)
    h0 = ts._transect_source(_thetas(2), n_cells, smoothed).T
    h, hu = _kernel_run(h0, b, ts.Grid(n_cells).steps)
    # the fluxes cancel between neighbours and vanish at the walls; what is
    # left is float32 rounding of each cell's update (6e-8 of a 4 km depth)
    # over 2,224 steps, which moves the scan's volume by 5e-6-8e-6 too
    np.testing.assert_allclose(np.asarray(h, np.float64).sum(1),
                               np.asarray(h0, np.float64).sum(1), rtol=2e-5)
    assert float(jnp.max(jnp.abs(hu))) > 1.0


def test_kernel_padded_chains_and_cells_stay_inert():
    # 200 cells: lanes padded up to 256; 3 chains: rows padded up to 8
    n_cells, steps = 200, 300
    b = ts._bathymetry_cached(n_cells, True)
    h0 = ts._transect_source(_thetas(3), n_cells, True).T
    w, n8 = ops.padded_width(n_cells), ops.padded_chains(3)
    assert (w, n8) == (256, 8)
    rows = jnp.asarray(ops.bathymetry_rows(b))
    _, state = transect_kernel(
        jnp.pad(h0, ((0, n8 - 3), (0, w - n_cells))), *(jnp.broadcast_to(r, (8, w)) for r in rows),
        n_cells=n_cells, strip=8, steps=steps, lam=_lam(n_cells, True),
        buoys=ts._buoy_rows(n_cells), thresh=ts.ARRIVAL_THRESH, keep_state=True,
        interpret=True)
    state = np.asarray(state)
    np.testing.assert_array_equal(state[:, 3:], 0.0)
    np.testing.assert_array_equal(state[:, :, n_cells:], 0.0)
    assert np.abs(state[1, :3, :n_cells]).max() > 1.0  # next to them the water moved
    # and the padded cells leave the real ones as an unpadded transect has them
    want = _kernel_run(h0, b, steps)
    np.testing.assert_array_equal(state[0, :3, :n_cells], want[0])


def test_cpu_takes_the_scan_and_the_chunk_span_names_it():
    assert jax.default_backend() == "cpu" and transect_path() == "xla"
    thetas = _thetas(2)
    np.testing.assert_array_equal(
        np.asarray(ts._solve_batch(thetas, 512, True)),
        np.asarray(jax.jit(ts._transect_observables, static_argnums=(1, 2, 3))(
            thetas, 512, True, "xla")))
    tracing.clear()
    with tracing.recording():
        TsunamiModel().evaluate_batch(np.asarray(thetas), {"level": 0})
    chunks = [r for r in tracing.records() if r.name == "model.chunk"]
    tracing.clear()
    assert chunks and all(c.attrs["kernel"] == "xla" for c in chunks)


def test_chunk_spans_name_the_kernel_for_evaluate_only(monkeypatch):
    """Where the platform's path is the kernel, evaluate chunks say so and
    derivative chunks, which run the scan's programs, say "xla"."""
    monkeypatch.setattr(ts.swe1d, "transect_path", lambda: "pallas")
    model, thetas = TsunamiModel(), np.asarray(_thetas(2))
    tracing.clear()
    with tracing.recording():
        model.evaluate_batch(thetas, {"level": 0})
        model.gradient_batch(thetas, np.ones((2, 4)), {"level": 0})
    kinds = [r.attrs["kernel"] for r in tracing.records() if r.name == "model.chunk"]
    tracing.clear()
    assert kinds == ["pallas", "xla"]


def _derivatives(path, thetas, senss, vecs):
    """The VJP (value and pull-back), JVP and HVP programs of the 1-D solve
    along `path`, written as `_vjp_batch`, `_jvp_batch` and `_hvp_batch`
    write them."""
    f = partial(ts._transect_observables, n_cells=512, smoothed=True, path=path)

    def vjp(th, s):
        y, pull = jax.vjp(f, th)
        return y, pull(s)[0]

    def hvp(th, s, v):
        return jax.grad(lambda t: jnp.sum(s * jax.jvp(f, (t,), (v,))[1]))(th)

    progs = {"vjp": (vjp, (thetas, senss)),
             "jvp": (lambda th, v: jax.jvp(f, (th,), (v,))[1], (thetas, vecs)),
             "hvp": (hvp, (thetas, senss, vecs))}
    return {k: (jax.make_jaxpr(p)(*a), jax.jit(p)(*a)) for k, (p, a) in progs.items()}


def test_derivatives_are_the_scans_bit_for_bit():
    """On the kernel's path the derivative programs are the scan's: the same
    numbers as `_vjp_batch`, `_jvp_batch` and `_hvp_batch`, and no kernel in
    them."""
    rng = np.random.default_rng(3)
    thetas = _thetas(8)
    senss = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    vecs = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
    got = _derivatives("pallas", thetas, senss, vecs)
    want = {"vjp": ts._vjp_batch(thetas, senss, 512, True),
            "jvp": ts._jvp_batch(thetas, vecs, 512, True),
            "hvp": ts._hvp_batch(thetas, senss, vecs, 512, True)}
    for op, (jaxpr, out) in got.items():
        assert "pallas_call" not in str(jaxpr), op
        for g, w in zip(jax.tree.leaves(out), jax.tree.leaves(want[op])):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=op)
    assert np.abs(np.asarray(want["vjp"][1])).max() > 0  # the heights pull back


def test_a_vmapped_solve_folds_into_the_chains():
    thetas = _thetas(8)
    solve = partial(ts._transect_observables, n_cells=512, smoothed=True, path="pallas")
    rows = jax.jit(jax.vmap(lambda t: solve(t[None])[0]))(thetas)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(jax.jit(solve)(thetas)))
    jaxpr = str(jax.make_jaxpr(jax.vmap(lambda t: solve(t[None])[0]))(thetas))
    assert jaxpr.count("pallas_call") == 1 and "f32[8,512]" in jaxpr


_SHARDED = """
import json, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.apps import tsunami as ts
mesh = Mesh(np.array(jax.devices()).reshape(4, 1), ("data", "model"))
sh = NamedSharding(mesh, P("data", None))
solve = partial(ts._transect_observables, n_cells=512, smoothed=True, path="pallas")
th = jnp.asarray(np.stack([np.linspace(40, 140, 16), np.full(16, 2.0)], 1), jnp.float32)
one = np.asarray(jax.jit(solve)(th))
run = jax.jit(solve, in_shardings=sh, out_shardings=sh)
with mesh:
    hlo = run.lower(th).compile().as_text()
    four = np.asarray(run(jax.device_put(th, sh)))
print(json.dumps({{"equal": bool(np.array_equal(one, four)), "all_gather": hlo.count("all-gather"),
                  "per_device": "f32[4,512]" in hlo}}))
"""


def test_under_a_mesh_each_device_solves_its_own_chains():
    """On four virtual devices the chains split over the mesh: the same rows
    as one device, and no gather of the chains in front of the kernel."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _SHARDED.format(src=str(ROOT / "src"))],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"equal": True, "all_gather": 0, "per_device": True}


@pytest.mark.parametrize("n,w,strip", [(16, 512, 16), (128, 512, 32), (16, 2048, 8),
                                       (128, 2048, 8), (8, 200, 8)])
def test_strips_tile_the_chains(n, w, strip):
    assert ops.strip_rows(n, ops.padded_width(w)) == strip and n % strip == 0
