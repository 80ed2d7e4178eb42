"""EvaluationFabric tests: caching, adaptive batching, HTTP /EvaluateBatch,
MLDA eval-count regression, and the ThreadedPool bug fixes it rides on."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import HTTPModel
from repro.core.fabric import (
    CallableBackend,
    EvaluationFabric,
    HTTPBackend,
    ModelBackend,
    SPMDBackend,
    ThreadedBackend,
    as_backend,
)
from repro.core.interface import JAXModel, Model
from repro.core.pool import ModelPool, ThreadedPool
from repro.core.server import serve_models
from repro.uq.mlda import mlda


class _CountingBatched:
    """Batched callable backend that counts points and calls."""

    def __init__(self):
        self.points = 0
        self.calls = 0

    def __call__(self, thetas):
        self.calls += 1
        self.points += len(thetas)
        return (np.asarray(thetas) ** 2).sum(axis=1, keepdims=True)


# -- backend coercion ---------------------------------------------------------


def test_as_backend_coercion():
    jm = JAXModel(lambda th: th * 2, 2, 2)
    assert isinstance(as_backend(ModelPool(jm)), SPMDBackend)
    assert isinstance(as_backend(jm), SPMDBackend)
    tp = ThreadedPool([jm], n_instances=None)
    assert isinstance(as_backend(tp), ThreadedBackend)
    assert isinstance(as_backend(lambda X: X), CallableBackend)
    tp.shutdown()
    with pytest.raises(TypeError):
        as_backend(42)


# -- cache semantics ----------------------------------------------------------


def test_cache_hits_dedupe_batches():
    f = _CountingBatched()
    with EvaluationFabric(f, cache_size=64) as fab:
        X = np.array([[1.0, 2.0], [3.0, 4.0], [1.0, 2.0]])  # one duplicate row
        out = fab.evaluate_batch(X)
        np.testing.assert_allclose(out.ravel(), [5.0, 25.0, 5.0])
        assert f.points == 2  # duplicate row evaluated once
        out2 = fab.evaluate_batch(X)  # fully cached
        np.testing.assert_allclose(out2, out)
        assert f.points == 2
        t = fab.telemetry()
        assert t["cache_hits"] == 4 and t["cache_misses"] == 2
        assert 0 < t["cache_hit_rate"] < 1


def test_cache_distinguishes_configs():
    calls = []

    def f(thetas, config):
        calls.append(dict(config or {}))
        return np.asarray(thetas) * float((config or {}).get("scale", 1.0))

    with EvaluationFabric(f, cache_size=64) as fab:
        a = fab.evaluate_batch([[2.0]], {"scale": 3.0})
        b = fab.evaluate_batch([[2.0]], {"scale": 5.0})
        assert a[0, 0] == 6.0 and b[0, 0] == 10.0
        assert len(calls) == 2  # same theta, different config -> both evaluated


def test_submit_serves_from_cache_and_coalesces():
    f = _CountingBatched()
    with EvaluationFabric(f, cache_size=64, linger_s=0.01) as fab:
        th = [1.5, -0.5]
        futs = [fab.submit(th) for _ in range(5)]  # identical in-flight
        vals = [float(ft.result()[0]) for ft in futs]
        assert all(v == vals[0] for v in vals)
        assert f.points == 1  # one real evaluation for 5 submits
        fut = fab.submit(th)  # now a cache hit: already-resolved future
        assert fut.done() and float(fut.result()[0]) == vals[0]
        assert fab.stats["coalesced"] >= 1
        assert fab.stats["cache_hits"] >= 1


def test_cache_disabled_reevaluates():
    f = _CountingBatched()
    with EvaluationFabric(f, cache_size=0) as fab:
        fab.evaluate_batch([[1.0, 1.0]])
        fab.evaluate_batch([[1.0, 1.0]])
        assert f.points == 2


# -- adaptive batching --------------------------------------------------------


def test_bursty_submits_pack_into_waves():
    f = _CountingBatched()
    with EvaluationFabric(f, cache_size=0, linger_s=0.01, max_batch=64) as fab:
        futs = [fab.submit([i * 0.1, 1.0]) for i in range(40)]
        for i, ft in enumerate(futs):
            np.testing.assert_allclose(
                ft.result()[0], (i * 0.1) ** 2 + 1.0, rtol=1e-6, atol=1e-9
            )
        assert fab.stats["points"] == 40
        assert fab.stats["waves"] < 40  # burst actually batched
        assert f.calls == fab.stats["waves"]


def test_adaptive_tuning_reacts_to_wave_latency():
    def slow(thetas):
        time.sleep(0.05)
        return np.asarray(thetas)

    fab = EvaluationFabric(slow, cache_size=0, linger_s=0.001, max_batch=2, adaptive=True)
    try:
        futs = [fab.submit([float(i)]) for i in range(8)]
        for ft in futs:
            ft.result()
        # slow waves (50 ms) must have pushed the linger window up from 1 ms
        assert fab.linger_s > 0.005
        # saturated waves must have grown the cap
        assert fab.max_batch > 2
    finally:
        fab.shutdown()


def test_wave_groups_by_config():
    seen = []

    def f(thetas, config):
        seen.append(((config or {}).get("level"), len(thetas)))
        return np.asarray(thetas)

    with EvaluationFabric(f, cache_size=0, linger_s=0.02) as fab:
        futs = [fab.submit([float(i)], {"level": i % 2}) for i in range(6)]
        for ft in futs:
            ft.result()
    levels = {lvl for lvl, _ in seen}
    assert levels == {0, 1}  # one backend call per distinct config per wave


# -- HTTP /EvaluateBatch ------------------------------------------------------


@pytest.fixture(scope="module")
def http_server():
    m = JAXModel(lambda th: jnp.array([jnp.sum(th**2), th[0] - th[1]]), 2, 2)
    server, _ = serve_models([m], 45873, background=True)
    yield "http://127.0.0.1:45873"
    server.shutdown()


def test_evaluate_batch_roundtrip(http_server):
    hm = HTTPModel(http_server, "forward")
    hm.round_trips = 0
    X = np.array([[1.0, 2.0], [3.0, 4.0], [0.5, -0.5]])
    out = hm.evaluate_batch(X)
    np.testing.assert_allclose(out[:, 0], (X**2).sum(1), rtol=1e-5)
    np.testing.assert_allclose(out[:, 1], X[:, 0] - X[:, 1], rtol=1e-5, atol=1e-6)
    assert hm.round_trips == 1  # ONE round-trip for the whole batch


def test_evaluate_batch_validates_sizes(http_server):
    hm = HTTPModel(http_server, "forward")
    with pytest.raises(RuntimeError, match="InvalidInput|inputs"):
        hm.evaluate_batch(np.ones((3, 5)))  # wrong input size


def test_fabric_http_backend_fans_out(http_server):
    clients = [HTTPModel(http_server), HTTPModel(http_server)]
    for c in clients:
        c.round_trips = 0
    with EvaluationFabric(HTTPBackend(clients), cache_size=0) as fab:
        X = np.random.default_rng(0).standard_normal((10, 2))
        out = fab.evaluate_batch(X)
        np.testing.assert_allclose(out[:, 0], (X**2).sum(1), rtol=1e-5)
    total = sum(c.round_trips for c in clients)
    assert total == 2  # one batched round-trip per client, not one per point


def test_evaluate_batch_fallback_against_legacy_server(http_server):
    hm = HTTPModel(http_server, "forward")
    hm._batch_supported = False  # pretend the server predates /EvaluateBatch
    hm.round_trips = 0
    X = np.array([[1.0, 2.0], [3.0, 4.0]])
    out = hm.evaluate_batch(X)
    np.testing.assert_allclose(out[:, 0], (X**2).sum(1), rtol=1e-5)
    assert hm.round_trips == len(X) + 1  # per-point fallback + /InputSizes


# -- MLDA eval-count regression ----------------------------------------------


def _run_mlda(cache_size: int):
    counter = _CountingBatched()

    def model(thetas, config):
        counter.calls += 1
        counter.points += len(thetas)
        shift = -0.5 if (config or {}).get("level") == 0 else 1.0
        return ((np.asarray(thetas) - shift) ** 2).sum(1, keepdims=True)

    fab = EvaluationFabric(model, cache_size=cache_size)
    try:
        res = mlda(
            None, np.zeros(2), 400, [4], 0.7 * np.eye(2),
            np.random.default_rng(0),
            fabric=fab,
            loglik=lambda out: -0.5 * float(out[0]),
            level_configs=[{"level": 0}, {"level": 1}],
        )
    finally:
        fab.shutdown()
    return res, counter.points


def test_mlda_caching_cuts_coarse_evals():
    """Same chain (same rng) with and without the fabric cache: identical
    samples and logpost-call accounting, strictly fewer model evaluations."""
    res_cached, evals_cached = _run_mlda(cache_size=4096)
    res_raw, evals_raw = _run_mlda(cache_size=0)
    np.testing.assert_allclose(res_cached.samples, res_raw.samples)
    assert res_cached.evals_per_level == res_raw.evals_per_level
    # without cache every logpost call reaches the model
    assert evals_raw == sum(res_raw.evals_per_level)
    # with cache, MLDA's repeated subchain states are deduped
    assert evals_cached < evals_raw
    # regression pin: the duplicate fraction is substantial (> 10 %)
    assert evals_cached <= 0.9 * evals_raw


# -- pool fixes the fabric rides on ------------------------------------------


class _Doubler(Model):
    def __init__(self, delay: float = 0.0, fail: bool = False):
        super().__init__("forward")
        self.delay = delay
        self.fail = fail
        self.calls = 0

    def get_input_sizes(self, c=None):
        return [1]

    def get_output_sizes(self, c=None):
        return [1]

    def supports_evaluate(self):
        return True

    def __call__(self, p, c=None):
        self.calls += 1
        if self.delay:
            time.sleep(self.delay)
        if self.fail:
            raise RuntimeError("instance down")
        return [[p[0][0] * 2]]


def test_threaded_pool_timers_cancelled_on_completion():
    """Completed requests must not leave deadline timers running (the seed
    leaked one live Timer thread per request until the deadline)."""
    pool = ThreadedPool([_Doubler() for _ in range(2)], deadline_s=30.0)
    pool.evaluate([[float(i)] for i in range(20)])
    time.sleep(0.2)  # cancelled timer threads exit promptly
    lingering = [
        t for t in threading.enumerate() if isinstance(t, threading.Timer)
    ]
    pool.shutdown()
    assert len(lingering) == 0


def test_speculative_respawn_shares_retry_budget():
    """A speculatively re-dispatched request shares the original's attempts
    counter (the seed gave the duplicate a fresh budget, doubling retries)."""
    insts = [_Doubler(delay=0.05, fail=True) for _ in range(2)]
    pool = ThreadedPool(insts, deadline_s=0.01, max_retries=2)
    fut = pool.submit([1.0])
    with pytest.raises(RuntimeError):
        fut.result(timeout=5.0)
    time.sleep(0.2)  # let any in-flight duplicates drain
    pool.shutdown()
    total = sum(i.calls for i in insts)
    # budget is max_retries + 1 = 3 (+1 tolerance for an in-flight speculative
    # duplicate); the seed's doubled budget gave 6+
    assert total <= 4, total


def test_model_pool_honors_x64():
    import jax

    with jax.enable_x64(True):
        m = JAXModel(lambda th: th * 1.0, 1, 1)
        pool = ModelPool(m)
        out = pool.evaluate(np.array([[1.0 + 1e-12]]))
        direct = np.asarray(m([[1.0 + 1e-12]])[0])
        assert out.dtype == np.float64
        np.testing.assert_array_equal(out.ravel(), direct.ravel())


# -- cache correctness under the training tap ---------------------------------


def test_concurrent_submits_with_observer_no_stale_hits():
    """Stress: 8 threads submitting heavily colliding thetas under two
    configs through a TINY LRU cache with a training tap attached. Every
    result must be correct for ITS (theta, config) — eviction churn and
    in-flight coalescing must never surface a stale or cross-config value —
    and the tap must see every model-computed point EXACTLY once."""
    lock = threading.Lock()
    observed = {"points": 0}
    computed = {"points": 0}

    def model(thetas, config):
        with lock:
            computed["points"] += len(thetas)
        scale = float((config or {}).get("scale", 1.0))
        return np.asarray(thetas).sum(1, keepdims=True) * scale

    fab = EvaluationFabric(model, cache_size=8)  # tiny: constant eviction

    @fab.record_observer
    def tap(op, thetas, outs, config):
        with lock:
            observed["points"] += len(thetas)

    errs = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(40):
            theta = np.round(rng.uniform(0, 1, 2) * 4) / 4  # heavy collisions
            scale = float(rng.integers(1, 3))
            got = float(fab.submit(theta, {"scale": scale}).result()[0])
            want = float(theta.sum() * scale)
            if abs(got - want) > 1e-9:
                errs.append((theta.tolist(), scale, got, want))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    misses = fab.stats["cache_misses"]
    fab.shutdown()
    assert not errs, errs[:5]
    # exactly-once tap semantics: each dispatched (= cache-missed) point is
    # observed once; cache hits and coalesced waiters are never replayed
    assert observed["points"] == computed["points"] == misses > 0


def test_capability_namespacing_and_eviction_under_observer():
    """With the tap attached and an LRU of 4: a gradient at theta never
    serves an evaluate at theta, and an EVICTED gradient entry is
    recomputed (observed again) rather than served stale."""
    jm = JAXModel(lambda th: th * 3.0, 2, 2)
    fab = EvaluationFabric(jm, cache_size=4)
    seen = []
    fab.record_observer(lambda op, th, o, c: seen.append(op))
    try:
        th = np.array([[1.0, 2.0]])
        sens = np.array([[1.0, 1.0]])
        ys = fab.evaluate_batch(th)
        gs = fab.gradient_batch(th, sens)
        np.testing.assert_allclose(ys.ravel(), [3.0, 6.0])
        np.testing.assert_allclose(gs.ravel(), [3.0, 3.0])
        np.testing.assert_allclose(fab.evaluate_batch(th), ys)  # own namespace
        # churn the 4-entry cache until the gradient entry is evicted
        for i in range(8):
            fab.evaluate_batch([[float(i) + 10.0, 0.0]])
        gs2 = fab.gradient_batch(th, sens)
        np.testing.assert_allclose(gs2, gs)  # recomputed, not stale
        assert seen.count("gradient") == 2  # eviction forced the re-dispatch
    finally:
        fab.shutdown()
