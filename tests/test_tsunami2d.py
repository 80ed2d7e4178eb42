"""The 2-D tsunami sea (`TsunamiModel(grids)` with 2-D `Grid`s) at small
grids: the served path against the plain reference of
`repro.kernels.swe.ref`, lake at rest, conservation of mass, padding, the
capability surface, the chunk spans and the sizes of the deployed levels;
and the time-loop kernel of `repro.kernels.swe2d` (interpret mode) against
the jnp step it shares its physics with."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import tsunami as ts
from repro.apps.tsunami import Grid, TsunamiModel
from repro.core import tracing
from repro.core.fabric import EvaluationFabric, ModelBackend
from repro.core.interface import pad_to_bucket
from repro.kernels.swe import swe2d_simulate_ref
from repro.kernels.swe2d import ops as swe2d_ops
from repro.kernels.swe2d import sea_kernel, sea_path, sea_solve

#: small levels of the sea: the smoothed bathymetry and the ridged one
SMALL = {0: Grid(32, 32, smoothed=True, t_end=2000.0),
         1: Grid(32, 32, smoothed=False, t_end=2000.0)}
#: one unit in the last place of float32 at the deepest still water (4 km):
#: the resolution of the elevation, which is depth minus still depth
DEPTH_ULP = float(np.spacing(np.float32(4000.0)))
#: small seas for the kernel: nx not a multiple of 128 (padded lanes), a dry
#: coast in the last columns, one strip or several
KERNEL_SEAS = {"smoothed": Grid(40, 32, smoothed=True, t_end=2000.0),
               "ridged": Grid(72, 48, smoothed=False, t_end=2000.0)}


def _thetas(n=4, seed=7):
    rng = np.random.default_rng(seed)
    return np.stack([rng.uniform(30, 150, n), rng.uniform(100, 300, n)], 1).astype(np.float32)


def _reference(theta, grid):
    """[4] observables of one source by the plain reference: the buoys'
    elevation after every step, then the first step above the threshold
    and the largest elevation."""
    b = jnp.asarray(ts._sea_bathymetry(grid.nx, grid.ny, grid.smoothed))
    h = ts._sea_source(jnp.asarray(theta[None]), grid)[0]
    etas = np.asarray(jax.jit(swe2d_simulate_ref, static_argnums=(2, 3, 4, 5))(
        h, b, grid.dt / (ts.L_DOMAIN / grid.nx), grid.dt / (ts.L_DOMAIN / grid.ny),
        grid.steps, ts._buoy_cells(grid)))
    out = []
    for j in range(etas.shape[1]):
        above = np.abs(etas[:, j]) > ts.ARRIVAL_THRESH
        out += [np.argmax(above) * grid.dt / 60 if above.any() else grid.t_end / 60,
                etas[:, j].max()]
    return np.array(out)


@pytest.mark.parametrize("level", [0, 1])
def test_served_path_matches_the_plain_reference(level):
    model = TsunamiModel(SMALL)
    fabric = EvaluationFabric(ModelBackend(model), cache_size=0)
    thetas = _thetas()
    try:
        got = fabric.evaluate_batch(thetas, {"level": level})
    finally:
        fabric.shutdown()
    want = np.stack([_reference(th, SMALL[level]) for th in thetas])
    assert model.stats[level] == len(thetas)
    # the two sum the same fluxes in another order: float32 reassociation
    # moves a depth of 4 km by a few units in the last place, and the first
    # step above the arrival threshold by at most one step where the
    # elevation lies within that rounding of the threshold
    np.testing.assert_allclose(got[:, 1::2], want[:, 1::2], rtol=0, atol=4 * DEPTH_ULP)
    np.testing.assert_allclose(got[:, ::2], want[:, ::2], rtol=0,
                               atol=SMALL[level].dt / 60 + 1e-5)
    # the waves reach the buoys: the comparison is not of still water
    assert np.any(got[:, ::2] < SMALL[level].t_end / 60)


@pytest.mark.parametrize("level", [0, 1])
def test_lake_at_rest_stays_at_rest(level):
    grid = SMALL[level]
    step = jax.jit(ts._sea_step(grid))
    b = jnp.asarray(ts._sea_bathymetry(grid.nx, grid.ny, grid.smoothed))
    still = jnp.maximum(-b, 0.0)[None]
    h, hu, hv = still, jnp.zeros_like(still), jnp.zeros_like(still)
    for _ in range(grid.steps):
        h, hu, hv = step(h, hu, hv)
    # the hydrostatic pressure g/2 h^2 (about 8e7 at 4 km) balances across
    # each face up to its float32 rounding (about 8); that moves momentum by
    # dt/dx times it a step, and the depth by a unit in the last place
    assert float(jnp.max(jnp.abs(h - still))) <= 2 * DEPTH_ULP
    assert float(jnp.max(jnp.abs(hu))) < 1.0 and float(jnp.max(jnp.abs(hv))) < 1.0
    # a source far outside the sea leaves the buoys still
    obs = TsunamiModel(SMALL).evaluate_batch([[1e4, 1e4]], {"level": level})[0]
    np.testing.assert_array_equal(obs[::2], np.float32(grid.t_end / 60))
    assert np.all(np.abs(obs[1::2]) <= 2 * DEPTH_ULP)


def test_mass_is_conserved_between_reflective_walls():
    grid = SMALL[1]
    step = jax.jit(ts._sea_step(grid))
    h = ts._sea_source(jnp.asarray(_thetas()), grid)
    hu = hv = jnp.zeros_like(h)
    before = np.asarray(h, np.float64).sum((1, 2))
    for _ in range(grid.steps):
        h, hu, hv = step(h, hu, hv)
    after = np.asarray(h, np.float64).sum((1, 2))
    # the fluxes cancel between neighbours and vanish at the walls; what
    # is left is float32 rounding of each cell's update, some 1e-8 of the
    # volume over the run
    np.testing.assert_allclose(after, before, rtol=1e-6)
    assert float(jnp.max(jnp.abs(hu))) > 1.0  # the water moved


def test_padded_chunk_equals_the_same_lanes_unpadded():
    grid = SMALL[0]
    thetas = _thetas(3)
    alone = np.asarray(ts._solve_batch_2d(jnp.asarray(thetas), grid))
    padded, pad = pad_to_bucket(thetas, 4)
    assert pad == 1
    np.testing.assert_array_equal(
        np.asarray(ts._solve_batch_2d(jnp.asarray(padded), grid))[:3], alone)
    model = TsunamiModel(SMALL)
    np.testing.assert_array_equal(model.evaluate_batch(thetas, {"level": 0}), alone)
    assert model.chunk_stats[0]["padded"] == 1


def test_2d_capabilities_declare_no_derivative_ops():
    model = TsunamiModel(SMALL)
    assert model.capabilities().names() == {"evaluate", "evaluate_batch"}
    assert TsunamiModel().capabilities().gradient_batch  # the transect keeps them
    with pytest.raises(NotImplementedError):
        model.gradient_batch(_thetas(1), np.ones((1, 4)))
    fabric = EvaluationFabric(ModelBackend(model), cache_size=0)
    try:
        with pytest.raises(Exception, match="gradient"):
            fabric.gradient_batch(_thetas(1), np.ones((1, 4)))
    finally:
        fabric.shutdown()
    # the per-point surface goes through the batch solver
    (row,) = model([_thetas(1)[0].tolist()], {"level": 0})
    np.testing.assert_array_equal(row, model.evaluate_batch(_thetas(1), {"level": 0})[0])


def test_levels_are_all_1d_or_all_2d():
    with pytest.raises(ValueError):
        TsunamiModel({0: Grid(512), 1: Grid(32, 32)})


def test_chunk_spans_carry_the_grids_work():
    tracing.clear()
    with tracing.recording():
        TsunamiModel(SMALL).evaluate_batch(_thetas(2), {"level": 1})
    chunks = [r for r in tracing.records() if r.name == "model.chunk"]
    tracing.clear()
    assert chunks and all(
        (c.attrs["level"], c.attrs["cells"], c.attrs["steps"]) == (1, 32 * 32, SMALL[1].steps)
        for c in chunks)


def test_deployed_levels_are_the_papers_smoothed_dof_and_four_times_it():
    lv0, lv1 = TsunamiModel.GRIDS_2D[0], TsunamiModel.GRIDS_2D[1]
    assert 3 * lv0.cells == 172_800 and lv0.smoothed
    assert 3 * lv1.cells == 691_200 and not lv1.smoothed
    assert (lv0.steps, lv1.steps) == (1042, 2085)
    # the 1-D levels keep the step counts of `_solve_batch`
    assert [Grid(n).steps for n in TsunamiModel.N_CELLS.values()] == [2224, 8899]


def test_serve_builds_the_2d_sea():
    from repro.launch.serve import build_model

    model = build_model("tsunami2d", "", False)
    assert model.two_d and model.grids == TsunamiModel.GRIDS_2D


def _kernel_run(grid, h, steps, strip=None):
    """(h, hu, hv) after `steps` kernel steps from the depth h at rest."""
    lam_x, lam_y = ts._courant(grid)
    *_, state = sea_solve(h, ts._sea_bathymetry(grid.nx, grid.ny, grid.smoothed), steps=steps,
                          lam_x=lam_x, lam_y=lam_y, buoys=ts._buoy_cells(grid),
                          thresh=ts.ARRIVAL_THRESH, strip=strip, keep_state=True)
    return state


def _scan_run(grid, h, steps):
    step = jax.jit(ts._sea_step(grid))
    state = h, jnp.zeros_like(h), jnp.zeros_like(h)
    for _ in range(steps):
        state = step(*state)
    return state


def _momentum_rounding(grid, steps):
    """How far float32 reassociation can move momentum in `steps` steps: a
    step adds dt/dx times the rounding of the hydrostatic pressure g/2 h^2
    (a unit in the last place at 4 km depth) along each axis."""
    lam_x, lam_y = ts._courant(grid)
    return steps * (lam_x + lam_y) * float(np.spacing(np.float32(0.5 * ts.G * 4000.0**2)))


@pytest.mark.parametrize("strip", [8, None], ids=["strip8", "strip_of_shape"])
@pytest.mark.parametrize("sea", sorted(KERNEL_SEAS))
def test_kernel_state_matches_the_jnp_step(sea, strip):
    grid, steps = KERNEL_SEAS[sea], 30
    h = ts._sea_source(jnp.asarray(_thetas(3)), grid)
    got = _kernel_run(grid, h, steps, strip)
    want = _scan_run(grid, h, steps)
    # the same operations in another fusion: float32 reassociation only
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=4 * DEPTH_ULP)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, w, rtol=0, atol=_momentum_rounding(grid, steps))
    assert float(jnp.max(jnp.abs(want[1]))) > 1.0  # the water moved


@pytest.mark.parametrize("sea", sorted(KERNEL_SEAS))
def test_kernel_observables_match_the_jnp_path(sea):
    grid = KERNEL_SEAS[sea]
    thetas = jnp.asarray(_thetas(3))
    solve = jax.jit(ts._sea_observables, static_argnames=("grid", "path"))
    got = np.asarray(solve(thetas, grid, "pallas"))
    want = np.asarray(solve(thetas, grid, "xla"))
    np.testing.assert_allclose(got[:, 1::2], want[:, 1::2], rtol=0, atol=4 * DEPTH_ULP)
    np.testing.assert_allclose(got[:, ::2], want[:, ::2], rtol=0, atol=grid.dt / 60 + 1e-5)
    assert np.any(want[:, ::2] < grid.t_end / 60)  # the waves reach the buoys


@pytest.mark.parametrize("sea", sorted(KERNEL_SEAS))
def test_kernel_keeps_a_lake_at_rest(sea):
    grid = KERNEL_SEAS[sea]
    b = ts._sea_bathymetry(grid.nx, grid.ny, grid.smoothed)
    still = jnp.asarray(np.maximum(-b, 0.0))[None]
    h, hu, hv = _kernel_run(grid, still, grid.steps)
    assert float(jnp.max(jnp.abs(h - still))) <= 2 * DEPTH_ULP
    assert float(jnp.max(jnp.abs(hu))) < 1.0 and float(jnp.max(jnp.abs(hv))) < 1.0


@pytest.mark.parametrize("sea", sorted(KERNEL_SEAS))
def test_kernel_conserves_mass_between_the_walls(sea):
    grid = KERNEL_SEAS[sea]
    h0 = ts._sea_source(jnp.asarray(_thetas(2)), grid)
    h, hu, _ = _kernel_run(grid, h0, grid.steps, strip=8)
    np.testing.assert_allclose(np.asarray(h, np.float64).sum((1, 2)),
                               np.asarray(h0, np.float64).sum((1, 2)), rtol=1e-6)
    assert float(jnp.max(jnp.abs(hu))) > 1.0


@pytest.mark.parametrize("sea", sorted(KERNEL_SEAS))
def test_kernel_padded_columns_stay_inert(sea):
    grid = KERNEL_SEAS[sea]
    h0, *bath = swe2d_ops.padded(ts._sea_source(jnp.asarray(_thetas(2)), grid),
                                 ts._sea_bathymetry(grid.nx, grid.ny, grid.smoothed))
    assert h0.shape[-1] % 128 == 0 and h0.shape[-1] > grid.nx
    lam_x, lam_y = ts._courant(grid)
    _, state = sea_kernel(h0, *bath, nx=grid.nx, strip=8, steps=grid.steps, lam_x=lam_x,
                          lam_y=lam_y, buoys=ts._buoy_cells(grid), thresh=ts.ARRIVAL_THRESH,
                          keep_state=True, interpret=True)
    state = np.asarray(state)
    np.testing.assert_array_equal(state[..., grid.nx:], 0.0)
    assert np.abs(state[:, 1:, :, :grid.nx]).max() > 1.0  # next to them the water moved


def test_cpu_takes_the_jnp_path_and_the_chunk_span_names_it():
    grid = SMALL[0]
    assert jax.default_backend() == "cpu" and sea_path(grid.ny, grid.nx) == "xla"
    solve = jax.jit(ts._sea_observables, static_argnames=("grid", "path"))
    thetas = _thetas(2)
    np.testing.assert_array_equal(np.asarray(ts._solve_batch_2d(jnp.asarray(thetas), grid)),
                                  np.asarray(solve(jnp.asarray(thetas), grid, "xla")))
    tracing.clear()
    with tracing.recording():
        TsunamiModel(SMALL).evaluate_batch(thetas, {"level": 0})
        TsunamiModel().evaluate_batch(_thetas(1), {"level": 0})
    chunks = [r for r in tracing.records() if r.name == "model.chunk"]
    tracing.clear()
    assert {c.attrs["cells"] for c in chunks} == {32 * 32, 512}
    assert all(c.attrs["kernel"] == "xla" for c in chunks)


@pytest.mark.parametrize("level,width,strip", [(0, 256, 48), (1, 512, 32)])
def test_strips_of_the_deployed_levels_tile_their_rows(level, width, strip):
    grid = TsunamiModel.GRIDS_2D[level]
    assert swe2d_ops.padded_width(grid.nx) == width
    assert swe2d_ops.strip_rows(grid.ny, width) == strip and grid.ny % strip == 0
