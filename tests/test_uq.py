"""UQ method tests: distributions, Sobol, sparse grids, KDE, GP, MCMC, MLDA."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.uq.distributions import (
    Beta,
    MultivariateNormal,
    Normal,
    Triangular,
    TruncatedNormal,
    Uniform,
)
from repro.uq import gp as gp_module
from repro.uq.gp import GP
from repro.uq.kde import kde, silverman_bandwidth
from repro.uq.mcmc import effective_sample_size, gelman_rubin, random_walk_metropolis
from repro.uq.mlda import delayed_acceptance, mlda
from repro.uq.monte_carlo import monte_carlo
from repro.uq.qmc import cub_qmc_sobol, sobol
from repro.uq.sensitivity import sobol_indices
from repro.uq import sparse_grid as sg

DISTS = [
    Uniform(-1.0, 3.0),
    Normal(0.5, 2.0),
    Beta(10.0, 10.0, -6.776, -5.544),  # the paper's draft distribution
    Triangular(0.25, 0.41),  # the paper's Froude distribution
    TruncatedNormal(0.0, 1.0, -1.5, 2.0),
]


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: type(d).__name__)
def test_pdf_integrates_to_one(dist):
    lo, hi = dist.support()
    xs = np.linspace(lo, hi, 20001)
    assert abs(np.trapezoid(dist.pdf(xs), xs) - 1.0) < 1e-3


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: type(d).__name__)
def test_ppf_is_inverse_cdf(dist):
    lo, hi = dist.support()
    us = np.linspace(0.01, 0.99, 25)
    xs = dist.ppf(us)
    # numeric CDF at ppf(u) == u
    grid = np.linspace(lo, hi, 40001)
    pdf = dist.pdf(grid)
    cdf = np.cumsum(pdf) * (grid[1] - grid[0])
    got = np.interp(xs, grid, cdf)
    np.testing.assert_allclose(got, us, atol=5e-3)


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: type(d).__name__)
def test_sampling_moments(dist, rng):
    s = dist.sample(rng, 40000)
    lo, hi = dist.support()
    xs = np.linspace(lo, hi, 20001)
    mean_ref = np.trapezoid(xs * dist.pdf(xs), xs)
    assert abs(s.mean() - mean_ref) < 0.05 * (hi - lo)


@pytest.mark.parametrize("dist", DISTS, ids=lambda d: type(d).__name__)
def test_logpdf_never_nan_on_boundary_inputs(dist):
    """log-pdf on support endpoints and outside points: finite or -inf,
    NEVER NaN (a NaN log-density silently poisons an MH accept ratio)."""
    lo, hi = dist.support()
    w = hi - lo
    pts = np.array([lo, hi, lo + 0.5 * w, lo - 0.5 * w, hi + 0.5 * w])
    lp = dist.logpdf(pts)
    assert not np.any(np.isnan(lp)), lp
    assert np.isfinite(lp[2])  # interior density is strictly positive
    if isinstance(dist, (Uniform, Beta, Triangular, TruncatedNormal)):
        # compact support: outside points are exactly -inf, not garbage
        assert lp[3] == -np.inf and lp[4] == -np.inf


def test_multivariate_normal_logpdf_matches_univariate(rng):
    mvn = MultivariateNormal((0.5,), (2.0,))
    ref = Normal(0.5, np.sqrt(2.0))
    xs = np.linspace(-3.0, 4.0, 7)
    np.testing.assert_allclose(
        mvn.logpdf(xs[:, None]), ref.logpdf(xs), rtol=1e-9, atol=1e-12
    )
    assert np.ndim(mvn.logpdf([0.1])) == 0  # single point -> scalar
    s = mvn.sample(rng, 4000)
    assert abs(s.mean() - 0.5) < 0.1
    assert abs(s.var() - 2.0) < 0.25


# -- Sobol --------------------------------------------------------------------


def test_sobol_matches_scipy():
    from scipy.stats import qmc as sq

    for d in (1, 2, 5, 13, 21):
        mine = sobol(128, d)
        ref = sq.Sobol(d, scramble=False).random(128)
        assert np.max(np.abs(mine - ref)) < 1e-8


def test_sobol_stratification():
    """(0,m,s)-net property: 2^4 points -> one per dyadic interval of size
    1/16 in each 1-d projection."""
    pts = sobol(16, 5)
    for j in range(5):
        cells = np.floor(pts[:, j] * 16).astype(int)
        assert sorted(cells) == list(range(16))


def test_sobol_scramble_preserves_uniformity(rng):
    pts = sobol(256, 3, scramble_seed=42)
    assert pts.shape == (256, 3)
    assert np.all((pts >= 0) & (pts < 1))
    assert abs(pts.mean() - 0.5) < 0.02


def test_cubature_converges():
    res = cub_qmc_sobol(lambda u: np.sin(2 * np.pi * u).sum(1, keepdims=True) + 1.0, 4, abs_tol=5e-4)
    assert res.converged
    assert abs(res.mean[0] - 1.0) < 5e-3


def test_cubature_rejects_single_replication():
    """Satellite regression: replications=1 used to burn the whole n_max
    budget and return se=NaN; it must be refused up front instead."""
    with pytest.raises(ValueError, match="replications"):
        cub_qmc_sobol(lambda u: u.sum(1, keepdims=True), 2, replications=1)


def test_cubature_shape_handling_is_explicit():
    """Scalar [N] returns and single-output [1, N] rows are accepted; any
    other row-count mismatch is a typed error, not a silent transpose."""
    res = cub_qmc_sobol(lambda u: u.sum(1), 2, abs_tol=1e-2)  # [N] ok
    assert abs(res.mean[0] - 1.0) < 0.05
    with pytest.raises(ValueError, match="expected"):
        cub_qmc_sobol(lambda u: np.ones((7, 2)), 2)


# -- Sobol' sensitivity indices -----------------------------------------------


def test_sobol_indices_match_analytic_ishigami():
    """First/total-order indices on the Ishigami function against the
    closed-form references, with the pick-freeze design riding the QMC
    doubling driver (n_evals == (dim + 2) x cubature points)."""
    a, b = 7.0, 0.1

    def f(U):
        X = np.pi * (2.0 * np.asarray(U) - 1.0)
        y = (np.sin(X[:, 0]) + a * np.sin(X[:, 1]) ** 2
             + b * X[:, 2] ** 4 * np.sin(X[:, 0]))
        return y[:, None]

    res = sobol_indices(f, 3, abs_tol=5e-3, n_max=2**13, seed=11)
    V = a**2 / 8 + b * np.pi**4 / 5 + b**2 * np.pi**8 / 18 + 0.5
    V1 = 0.5 * (1 + b * np.pi**4 / 5) ** 2
    V2 = a**2 / 8
    T3 = 8 * b**2 * np.pi**8 / 225
    np.testing.assert_allclose(res.variance, V, rtol=0.02)
    np.testing.assert_allclose(res.first, [V1 / V, V2 / V, 0.0], atol=0.02)
    np.testing.assert_allclose(
        res.total, [(V1 + T3) / V, V2 / V, T3 / V], atol=0.02
    )
    assert res.n_evals == 5 * res.cubature.n_evals  # A, B and AB_i per point


def test_sobol_indices_one_wave_per_doubling_through_fabric():
    """Through an EvaluationFabric the (dim + 2) pick-freeze blocks of each
    doubling land as ONE evaluate wave, never dim + 2 dispatches."""
    from repro.core.fabric import CallableBackend, EvaluationFabric

    calls = {"waves": 0}

    def g(U):
        calls["waves"] += 1
        U = np.atleast_2d(U)
        return (U[:, :1] + 2.0 * U[:, 1:2] ** 2)

    with EvaluationFabric(CallableBackend(g), cache_size=0) as fab:
        res = sobol_indices(
            f=fab, dim=2, abs_tol=5e-3, n_init=64, n_max=2**10,
            replications=4, seed=3,
        )
    # x1 linear (V1 = 1/12), x2 quadratic (V2 = 16/45), no interaction
    V1, V2 = 1.0 / 12.0, 16.0 / 45.0
    np.testing.assert_allclose(
        res.first, [V1 / (V1 + V2), V2 / (V1 + V2)], atol=0.03
    )
    np.testing.assert_allclose(res.first, res.total, atol=0.03)
    # one wave per (replication x doubling) — NEVER x(dim + 2) on top
    assert calls["waves"] == 4 * len(res.cubature.history)


def test_sobol_indices_validates_dimension_and_variance():
    with pytest.raises(ValueError, match="2\\*dim"):
        sobol_indices(lambda U: U[:, :1], 99)
    with pytest.raises(ValueError, match="variance"):
        sobol_indices(lambda U: np.ones((len(U), 1)), 2, n_max=256,
                      replications=4)


# -- sparse grids -------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    coefs=st.lists(st.floats(-2, 2), min_size=6, max_size=6),
    w=st.integers(min_value=3, max_value=5),
)
def test_sparse_grid_polynomial_exactness(coefs, w):
    """Total-degree-w Smolyak-Leja grids reproduce total-degree-w polys."""
    c = np.asarray(coefs)

    def f(X):
        x, y = X[:, 0], X[:, 1]
        return (c[0] + c[1] * x + c[2] * y + c[3] * x * y + c[4] * x**2 + c[5] * y**2)[:, None]

    kf = [sg.knots_uniform_leja(-1, 1), sg.knots_uniform_leja(-1, 1)]
    S = sg.smolyak_grid(2, w, kf)
    Sr = sg.reduce_sparse_grid(S)
    vals = sg.evaluate_on_sparse_grid(f, Sr)
    xq = np.random.default_rng(1).uniform(-1, 1, (20, 2))
    np.testing.assert_allclose(sg.interpolate_on_sparse_grid(S, Sr, vals, xq), f(xq), atol=1e-8)


def test_sparse_grid_nested_reuse():
    kf = [sg.knots_uniform_leja(-1, 1)] * 2
    S1 = sg.smolyak_grid(2, 3, kf)
    Sr1 = sg.reduce_sparse_grid(S1)
    S2 = sg.smolyak_grid(2, 5, kf)
    Sr2 = sg.reduce_sparse_grid(S2)
    calls = {"n": 0}

    def f(X):
        calls["n"] += len(X)
        return np.sum(X, axis=1, keepdims=True)

    v1 = sg.evaluate_on_sparse_grid(f, Sr1)
    n1 = calls["n"]
    sg.evaluate_on_sparse_grid(f, Sr2, previous=(Sr1, v1))
    assert calls["n"] - n1 == len(Sr2.points) - len(Sr1.points)  # strict nesting


def test_leja_knots_are_nested_and_in_support():
    kn = sg.knots_beta_leja(10, 10, -6.776, -5.544)
    k5, k9 = kn(5), kn(9)
    np.testing.assert_allclose(k9[:5], k5)
    assert np.all(k9 >= -6.776) and np.all(k9 <= -5.544)


# -- KDE ----------------------------------------------------------------------


def test_kde_integral_and_positive_support(rng):
    s = rng.lognormal(0.5, 0.3, 4000)
    d, p = kde(s, support="positive", n_points=500)
    assert np.all(p > 0)
    assert abs(np.trapezoid(d, p) - 1.0) < 0.02


def test_kde_bandwidth_selection_on_gaussian_mixture(rng):
    """Silverman's rule on a known bimodal mixture: the selected bandwidth
    must be positive and narrow enough that the KDE keeps both modes
    separated (a spread-scale bandwidth would merge them), while the
    density still normalizes."""
    n = 4000
    comp = rng.uniform(size=n) < 0.5
    s = np.where(comp, rng.normal(-2.0, 0.5, n), rng.normal(2.0, 0.5, n))
    h = silverman_bandwidth(s)
    assert 0.0 < h < np.std(s)
    d, p = kde(s, n_points=400)  # bandwidth=None -> Silverman
    assert abs(np.trapezoid(d, p) - 1.0) < 0.02
    modes = np.interp([-2.0, 2.0], p, d)
    valley = np.interp(0.0, p, d)
    assert min(modes) > 2.0 * valley  # bimodality recovered
    # an explicit narrower bandwidth sharpens the modes further
    d_sharp, p_sharp = kde(s, bandwidth=0.1, n_points=400)
    assert np.interp(-2.0, p_sharp, d_sharp) > 0.95 * np.interp(-2.0, p, d)


# -- GP -----------------------------------------------------------------------


def test_gp_interpolates_training_points(rng):
    X = rng.uniform(-1, 1, (25, 2))
    y = np.sin(3 * X[:, 0]) * np.cos(2 * X[:, 1])
    gp = GP.fit(X, y, n_iters=200)
    np.testing.assert_allclose(gp.predict(X), y, atol=5e-3)
    mu, var = gp.predict(X, return_var=True)
    assert np.all(var >= 0)


def test_gp_ard_lengthscales_detect_irrelevant_dim(rng):
    X = rng.uniform(-1, 1, (60, 2))
    y = np.sin(4 * X[:, 0])  # dim 1 irrelevant
    gp = GP.fit(X, y, n_iters=300)
    ls = np.exp(gp.log_params[:2])
    assert ls[1] > 1.5 * ls[0]  # ARD: irrelevant dim gets longer lengthscale


def test_gp_predict_variance_floor_on_degenerate_training(rng):
    """Regression: on a near-degenerate training set (every point repeated
    three times) the Schur complement amp - v^T v is pure round-off at the
    training points and used to come back 0 or slightly negative — and a
    DA screen that takes log/sqrt of the predictive variance NaNs on it.
    The variance must now be strictly positive with a finite log."""
    base = rng.uniform(-1, 1, (10, 2))
    X = np.repeat(base, 3, axis=0)
    y = np.sin(2 * X[:, 0]) + X[:, 1]
    gp = GP.fit(X, y, n_iters=150)
    mu, var = gp.predict(np.vstack([base, [[0.0, 0.0]], [[5.0, -5.0]]]),
                         return_var=True)
    assert np.all(var > 0)
    assert np.all(np.isfinite(np.log(var)))
    assert np.all(np.isfinite(mu))


def test_gp_from_params_matches_fit_factorization(rng):
    """The online refit path (fixed hyperparameters, one fresh Cholesky)
    must reproduce the offline fit exactly on the same window."""
    X = rng.uniform(-1, 1, (30, 2))
    y = np.cos(3 * X[:, 0]) * X[:, 1]
    gp = GP.fit(X, y, n_iters=150)
    gp2 = GP.from_params(X, y, gp.log_params)
    Xq = rng.uniform(-1, 1, (15, 2))
    np.testing.assert_allclose(gp.predict(Xq), gp2.predict(Xq), rtol=1e-10)
    m1, v1 = gp.predict(Xq, return_var=True)
    m2, v2 = gp2.predict(Xq, return_var=True)
    np.testing.assert_allclose(v1, v2, rtol=1e-8)


def _matern52_f64(A, B, ls, amp):
    """The Matern-5/2 ARD kernel in float64 NumPy."""
    d = (A[:, None, :] - B[None, :, :]) / ls
    r2 = np.sum(d * d, axis=-1)
    s5r = np.sqrt(5.0 * r2)
    return amp * (1.0 + s5r + 5.0 * r2 / 3.0) * np.exp(-s5r)


@pytest.mark.parametrize("Q,n", [(1, 6), (3, 25), (128, 128), (130, 60)])
def test_gp_predict_matches_float64_reference(rng, Q, n):
    """The compiled, padded kernel path against a float64 NumPy posterior
    built from the GP's own hyperparameters and factorization; a padded
    query returns what the same rows return inside a full power-of-two
    block."""
    X = rng.uniform(-1, 1, (n, 2))
    gp = GP.from_params(X, np.sin(3 * X[:, 0]) * np.cos(2 * X[:, 1]),
                        np.array([np.log(0.6), np.log(0.4), 0.3, np.log(1e-6), 0.1]))
    Xq = rng.uniform(-1, 1, (Q, 2)).astype(np.float32)
    mu, var = gp.predict(Xq, return_var=True)

    p = gp.log_params
    amp = np.exp(p[2])
    Ks = _matern52_f64(Xq.astype(float), gp.X.astype(float), np.exp(p[:2]), amp)
    mu_ref = gp._ymu + gp._ysd * (p[4] + Ks @ gp._alpha)
    v = np.linalg.solve(gp._chol, Ks.T)
    var_ref = np.maximum(amp - np.sum(v * v, axis=0), 1e-9 * amp) * gp._ysd**2
    # float32 kernel entries are off by a few ulps of amp; the mean carries
    # that through alpha, the variance through the triangular solve
    eps = np.finfo(np.float32).eps * amp
    np.testing.assert_allclose(
        mu, mu_ref, rtol=0, atol=8 * eps * gp._ysd * np.abs(gp._alpha).sum())
    np.testing.assert_allclose(var, var_ref, rtol=0, atol=256 * eps * gp._ysd**2)

    block = max(8, 1 << (Q - 1).bit_length())
    Xb = np.vstack([Xq, rng.uniform(-1, 1, (block - Q, 2))])
    mu_b, var_b = gp.predict(Xb, return_var=True)
    np.testing.assert_allclose(mu_b[:Q], mu, rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(var_b[:Q], var, rtol=1e-12, atol=1e-14)


def test_gp_predict_compiles_one_program_per_query_bucket(rng):
    """Query blocks of every size from 1 to 130 rows share the six
    power-of-two buckets 8, 16, ..., 256: one compile per bucket, never
    one per size."""
    X = rng.uniform(-1, 1, (20, 3))
    gp = GP.from_params(X, X.sum(axis=1), np.array([0.0, 0.0, 0.0, 0.0, np.log(1e-4), 0.0]))
    before = gp_module._kernel._cache_size()
    for Q in range(1, 131):
        assert gp.predict(rng.uniform(-1, 1, (Q, 3))).shape == (Q,)
    assert gp_module._kernel._cache_size() - before <= 6


def test_gp_kernel_exp_is_float32_accurate():
    """The kernel's own exponential holds to 2 float32 ulp of e**x over the
    range a float32 result covers, on any backend (it does not lean on the
    backend's `exp`), and its derivative is its value."""
    import jax
    import jax.numpy as jnp

    x = np.linspace(-87.0, 88.0, 400_001).astype(np.float32)
    want = np.exp(x.astype(np.float64))
    got = np.asarray(jax.jit(gp_module._exp)(x), np.float64)
    assert np.max(np.abs(got - want) / want) <= 2 * 2.0**-24
    xs = jnp.asarray(x[::1000])
    grad = jax.grad(lambda t: gp_module._exp(t).sum())(xs)
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(gp_module._exp(xs)))


# -- MCMC / MLDA ----------------------------------------------------------------


def test_rwm_recovers_gaussian(rng):
    lp = lambda x: -0.5 * float(np.sum(x**2))
    r = random_walk_metropolis(lp, np.zeros(2), 6000, 1.4 * np.eye(2), rng, adaptive=True)
    s = r.samples[1000:]
    assert np.all(np.abs(s.mean(0)) < 0.15)
    assert np.all(np.abs(s.var(0) - 1.0) < 0.2)
    assert 0.1 < r.accept_rate < 0.6
    assert effective_sample_size(s[:, 0]) > 100


def test_mlda_matches_fine_posterior(rng):
    """2-level MLDA with a biased coarse model still targets the fine
    posterior (the DA correction removes coarse bias)."""
    lp_fine = lambda x: -0.5 * float(np.sum((x - 1.0) ** 2))
    lp_coarse = lambda x: -0.5 * float(np.sum((x + 0.5) ** 2 / 2.0))  # wrong mean+var
    res = mlda([lp_coarse, lp_fine], np.zeros(2), 5000, [4], 0.7 * np.eye(2), rng)
    s = res.samples[500:]
    assert np.all(np.abs(s.mean(0) - 1.0) < 0.15)
    assert np.all(np.abs(s.var(0) - 1.0) < 0.25)
    # coarse level was actually used for proposals
    assert res.evals_per_level[0] > res.evals_per_level[1]


def test_mlda_three_levels(rng):
    lp2 = lambda x: -0.5 * float(np.sum(x**2))
    lp1 = lambda x: -0.5 * float(np.sum((x - 0.2) ** 2 / 1.2))
    lp0 = lambda x: -0.5 * float(np.sum((x + 0.3) ** 2 / 1.5))
    res = mlda([lp0, lp1, lp2], np.zeros(1), 3000, [5, 3], np.eye(1), rng)
    s = res.samples[300:]
    assert abs(s.mean()) < 0.15
    assert res.evals_per_level[0] > res.evals_per_level[1] > res.evals_per_level[2]


def test_monte_carlo_ci(rng):
    res = monte_carlo(
        lambda X: (X**2).sum(1, keepdims=True),
        lambda r, n: r.standard_normal((n, 3)),
        4000,
        rng,
    )
    assert abs(res.mean[0] - 3.0) < 4 * res.std_error[0] + 0.05
