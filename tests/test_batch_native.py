"""Batch-native evaluation tests: batched == per-point for the app models,
lockstep ensemble samplers match their sequential counterparts, dispatch
layers bucket + advertise the capability, ThreadedPool partial failures."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.composite import CompositeModel
from repro.apps import tsunami as ts
from repro.apps.tsunami import TsunamiModel
from repro.core.client import HTTPModel
from repro.core.fabric import EvaluationFabric, ModelBackend
from repro.core.interface import JAXModel, Model, next_pow2, pad_to_bucket
from repro.core.pool import ModelPool, ThreadedPool
from repro.core.server import serve_models
from repro.kernels.swe import swe_simulate_ref
from repro.uq.mcmc import (
    batched_logpost,
    ensemble_pcn,
    ensemble_random_walk_metropolis,
    random_walk_metropolis,
)

RNG = np.random.default_rng(42)
TSUNAMI_THETAS = np.stack(
    [RNG.uniform(40.0, 140.0, 6), RNG.uniform(0.8, 3.5, 6)], axis=1
)


# -- helpers ------------------------------------------------------------------


def test_next_pow2_and_padding():
    assert [next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    x = np.arange(6, dtype=float).reshape(3, 2)
    padded, pad = pad_to_bucket(x, 8)
    assert padded.shape == (8, 2) and pad == 5
    np.testing.assert_array_equal(padded[3:], np.tile(x[-1:], (5, 1)))
    same, none = pad_to_bucket(x, 3)
    assert none == 0 and same is x


# -- app model equivalence ----------------------------------------------------


@pytest.fixture(scope="module")
def tsunami():
    return TsunamiModel()


def _transect_reference(theta, grid):
    """[4] observables of one source by the plain per-source reference: the
    buoys' elevation after every step (`swe_simulate_ref`), then the first
    step above the threshold and the largest elevation."""
    dx = ts.L_DOMAIN / grid.nx
    x = (np.arange(grid.nx) + 0.5) * dx
    b = ts.bathymetry(x, grid.smoothed).astype(np.float32)
    h0 = np.maximum(-b, 0.0)
    eta0 = theta[1] * np.exp(-(((x - theta[0] * 1e3) / 25e3) ** 2))
    h = np.maximum(h0 + eta0 * (h0 > ts.H_DRY), 0.0).astype(np.float32)
    rows = tuple(int(bk * 1e3 / dx) for bk in ts.BUOYS_KM)
    etas = np.asarray(jax.jit(swe_simulate_ref, static_argnums=(2, 3, 4))(
        jnp.asarray(h), jnp.asarray(b), grid.dt / dx, grid.steps, rows))
    out = []
    for j in range(etas.shape[1]):
        above = np.abs(etas[:, j]) > ts.ARRIVAL_THRESH
        out += [np.argmax(above) * grid.dt / 60 if above.any() else grid.t_end / 60,
                etas[:, j].max()]
    return np.array(out)


def test_tsunami_batch_matches_sequential_coarse(tsunami):
    seq = np.array([_transect_reference(t, tsunami.grids[0]) for t in TSUNAMI_THETAS])
    bat = tsunami.evaluate_batch(TSUNAMI_THETAS, {"level": 0})
    # arrival times (cols 0, 2) agree to one timestep; heights (1, 3) to
    # float32-reassociation accumulation over ~2e3 nonlinear steps
    np.testing.assert_allclose(bat[:, [0, 2]], seq[:, [0, 2]], atol=0.05)
    np.testing.assert_allclose(bat[:, [1, 3]], seq[:, [1, 3]], rtol=2e-2)


def test_tsunami_batch_matches_sequential_fine(tsunami):
    thetas = TSUNAMI_THETAS[:2]
    seq = np.array([_transect_reference(t, tsunami.grids[1]) for t in thetas])
    bat = tsunami.evaluate_batch(thetas, {"level": 1})
    np.testing.assert_allclose(bat[:, [0, 2]], seq[:, [0, 2]], atol=0.05)
    np.testing.assert_allclose(bat[:, [1, 3]], seq[:, [1, 3]], rtol=5e-2)


def test_tsunami_batch_any_size(tsunami):
    """Non-power-of-2 and sub-chunk batch sizes pad internally and trim."""
    out5 = tsunami.evaluate_batch(TSUNAMI_THETAS[:5], {"level": 0})
    out1 = tsunami.evaluate_batch(TSUNAMI_THETAS[:1], {"level": 0})
    assert out5.shape == (5, 4) and out1.shape == (1, 4)
    np.testing.assert_allclose(out5[0], out1[0], rtol=1e-6)


@pytest.fixture(scope="module")
def composite():
    return CompositeModel()


def test_composite_batch_matches_sequential_rom(composite):
    thetas = np.array(
        [[77.5, 210.0, 10.0], [70.0, 180.0, 25.0], [85.0, 240.0, 15.0]]
    )
    seq = np.array([composite([list(t)])[0][0] for t in thetas])
    bat = composite.evaluate_batch(thetas).ravel()
    np.testing.assert_allclose(bat, seq, rtol=1e-4)


def test_composite_batch_matches_sequential_full(composite):
    thetas = np.array([[77.5, 210.0, 10.0], [78.0, 180.0, 30.0]])
    seq = np.array([composite([list(t)], {"mode": "full"})[0][0] for t in thetas])
    bat = composite.evaluate_batch(thetas, {"mode": "full"}).ravel()
    np.testing.assert_allclose(bat, seq, rtol=1e-5)


def test_jaxmodel_batch_pads_pow2():
    m = JAXModel(lambda th: jnp.atleast_1d(jnp.sum(th**2)), 3, 1)
    X = np.arange(15, dtype=float).reshape(5, 3)  # 5 -> bucket 8
    out = m.evaluate_batch(X)
    np.testing.assert_allclose(out.ravel(), (X**2).sum(1), rtol=1e-5)


# -- ensemble samplers --------------------------------------------------------


def test_ensemble_rwm_matches_sequential_statistics():
    """Lockstep RWM reproduces sequential RWM's acceptance rate and
    posterior moments on a standard Gaussian target."""
    rng = np.random.default_rng(0)
    lp_batch = lambda X: -0.5 * np.sum(np.atleast_2d(X) ** 2, axis=1)
    x0s = rng.standard_normal((12, 2))
    res = ensemble_random_walk_metropolis(lp_batch, x0s, 2500, 1.4 * np.eye(2), rng)
    assert res.samples.shape == (12, 2500, 2)
    assert res.n_waves == 2501  # ONE wave per step
    s = res.samples[:, 500:].reshape(-1, 2)

    seq = random_walk_metropolis(
        lambda x: -0.5 * float(np.sum(x**2)),
        np.zeros(2), 2500, 1.4 * np.eye(2), np.random.default_rng(1),
    )
    assert abs(res.accept_rate - seq.accept_rate) < 0.08
    assert np.all(np.abs(s.mean(0)) < 0.1)
    assert np.all(np.abs(s.var(0) - 1.0) < 0.15)
    # per-chain view is interchangeable with run_chains output
    chains = res.chains()
    assert len(chains) == 12 and chains[0].samples.shape == (2500, 2)


def test_ensemble_pcn_targets_posterior():
    """pCN with N(0,I) prior and Gaussian likelihood -> posterior N(0, I/2)."""
    rng = np.random.default_rng(3)
    ll_batch = lambda X: -0.5 * np.sum(np.atleast_2d(X) ** 2, axis=1)
    x0s = rng.standard_normal((10, 2))
    res = ensemble_pcn(
        ll_batch, lambda r, k: r.standard_normal((k, 2)), x0s, 2000, 0.5, rng
    )
    s = res.samples[:, 400:].reshape(-1, 2)
    assert np.all(np.abs(s.mean(0)) < 0.1)
    assert np.all(np.abs(s.var(0) - 0.5) < 0.12)


def test_batched_logpost_masks_out_of_prior():
    calls = {"points": 0}

    def model_batch(X):
        calls["points"] += len(X)
        return np.sum(np.atleast_2d(X) ** 2, axis=1, keepdims=True)

    lp = batched_logpost(
        model_batch,
        loglik=lambda y: -0.5 * float(y[0]),
        logprior=lambda t: 0.0 if np.all(np.abs(t) < 1.0) else -np.inf,
    )
    X = np.array([[0.5, 0.0], [5.0, 0.0], [-0.2, 0.3]])
    out = lp(X)
    assert out[1] == -np.inf and np.all(np.isfinite(out[[0, 2]]))
    assert calls["points"] == 2  # the out-of-prior row never reached the model


# -- dispatch layers ----------------------------------------------------------


def test_fabric_routes_native_batch_without_fallback():
    m = JAXModel(lambda th: th * 3.0, 2, 2)
    with EvaluationFabric(ModelBackend(m), cache_size=0) as fab:
        X = np.random.default_rng(0).standard_normal((10, 2))
        out = fab.evaluate_batch(X)
        np.testing.assert_allclose(out, X * 3.0, rtol=1e-5)
        back = fab.telemetry()["backend"]
        assert back["native"] is True
        assert back["native_batches"] == 1 and back["native_points"] == 10
        assert back["fallback_points"] == 0
        assert back["padded"] == 0  # JAXModel buckets internally


class _NativeNoBucket(Model):
    """Native batch model that jits over the batch but does NOT self-pad —
    it opts into dispatcher-level bucketing via batch_bucket."""

    batch_bucket = True

    def __init__(self):
        super().__init__("forward")
        self.seen_sizes: list[int] = []

    def get_input_sizes(self, c=None):
        return [2]

    def get_output_sizes(self, c=None):
        return [1]

    def supports_evaluate(self):
        return True

    def supports_evaluate_batch(self):
        return True

    def evaluate_batch(self, thetas, config=None):
        thetas = np.atleast_2d(thetas)
        self.seen_sizes.append(len(thetas))
        return np.sum(thetas**2, axis=1, keepdims=True)


def test_fabric_buckets_models_that_ask_for_it():
    m = _NativeNoBucket()
    with EvaluationFabric(ModelBackend(m), cache_size=0) as fab:
        X = np.random.default_rng(2).standard_normal((10, 2))
        out = fab.evaluate_batch(X)
        np.testing.assert_allclose(out.ravel(), (X**2).sum(1), rtol=1e-6)
        assert m.seen_sizes == [16]  # wave padded to the pow2 bucket
        back = fab.telemetry()["backend"]
        assert back["padded"] == 6 and back["native_batches"] == 1


class _PerPointOnly(Model):
    def __init__(self):
        super().__init__("forward")

    def get_input_sizes(self, c=None):
        return [2]

    def get_output_sizes(self, c=None):
        return [1]

    def supports_evaluate(self):
        return True

    def __call__(self, p, c=None):
        return [[float(np.sum(np.asarray(p[0]) ** 2))]]


def test_fabric_counts_fallback_for_per_point_models():
    with EvaluationFabric(ModelBackend(_PerPointOnly()), cache_size=0) as fab:
        X = np.random.default_rng(1).standard_normal((6, 2))
        out = fab.evaluate_batch(X)
        np.testing.assert_allclose(out.ravel(), (X**2).sum(1), rtol=1e-6)
        back = fab.telemetry()["backend"]
        assert back["native"] is False
        assert back["native_batches"] == 0 and back["fallback_points"] == 6


def test_model_pool_pow2_bucketing():
    m = JAXModel(lambda th: th * 2.0, 2, 2)
    pool = ModelPool(m)
    out = pool.evaluate(np.ones((5, 2)))
    assert out.shape == (5, 2)
    bucket = next_pow2(5) + (-next_pow2(5)) % pool.n_instances
    assert pool.stats["padded"] == bucket - 5
    pool.evaluate(np.ones((6, 2)))  # same bucket -> no new jit shape
    assert pool.stats["bucket_shapes"] == 1


def test_modelinfo_advertises_evaluate_batch():
    m = JAXModel(lambda th: jnp.atleast_1d(jnp.sum(th**2)), 2, 1)
    server, _ = serve_models([m], 45877, background=True)
    try:
        hm = HTTPModel("http://127.0.0.1:45877", "forward")
        assert hm.supports_evaluate_batch() is True
        assert hm._batch_supported is True  # probing skipped entirely
        hm.round_trips = 0
        out = hm.evaluate_batch(np.ones((4, 2)))
        assert hm.round_trips == 1
        np.testing.assert_allclose(out.ravel(), [2.0] * 4, rtol=1e-5)
    finally:
        server.shutdown()


# -- ThreadedPool shared-deadline collection ----------------------------------


class _Flaky(Model):
    """Fails on theta[0] > 0; optional fixed delay."""

    def __init__(self, delay: float = 0.0):
        super().__init__("forward")
        self.delay = delay

    def get_input_sizes(self, c=None):
        return [1]

    def get_output_sizes(self, c=None):
        return [1]

    def supports_evaluate(self):
        return True

    def __call__(self, p, c=None):
        if self.delay:
            time.sleep(self.delay)
        if p[0][0] > 0:
            raise RuntimeError("instance rejects positive theta")
        return [[p[0][0] * 2.0]]


def test_threaded_pool_surfaces_failing_indices():
    pool = ThreadedPool([_Flaky() for _ in range(2)], max_retries=0)
    try:
        with pytest.raises(RuntimeError, match=r"theta indices \[1, 3\]"):
            pool.evaluate([[-1.0], [2.0], [-3.0], [4.0]])
    finally:
        pool.shutdown()


def test_threaded_pool_shared_deadline_does_not_serialize():
    """The overall deadline spans the wave: slow-but-successful points on
    later indices still complete while an early point fails."""
    pool = ThreadedPool([_Flaky(delay=0.05) for _ in range(4)], max_retries=0)
    try:
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="1/8 points failed"):
            pool.evaluate([[-1.0]] * 4 + [[5.0]] + [[-1.0]] * 3, timeout_s=10.0)
        # 8 points, 4 workers, 50 ms each -> ~0.1 s; far below the deadline
        assert time.monotonic() - t0 < 5.0
    finally:
        pool.shutdown()


def test_threaded_pool_deadline_times_out_stragglers():
    pool = ThreadedPool([_Flaky(delay=30.0)], max_retries=0)
    try:
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="deadline"):
            pool.evaluate([[-1.0]], timeout_s=0.3)
        assert time.monotonic() - t0 < 5.0
    finally:
        pool._stop.set()  # worker is sleeping; don't join for 30 s
