"""Capability-typed model interface v2: descriptor semantics, negotiation
(server ⊆ client, router routing + steal refusal), per-capability cache
isolation, FD fallback step sizing, batched AD surfaces, and the
gradient-based lockstep samplers (MALA / HMC / pooled Haario adaptation)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import HTTPModel
from repro.core.fabric import (
    CallableBackend,
    EvaluationFabric,
    FabricRouter,
    ModelBackend,
)
from repro.core.interface import (
    Capabilities,
    JAXModel,
    Model,
    UnsupportedCapability,
    model_capabilities,
)
from repro.core.protocol import ModelSupport
from repro.core.server import serve_models
from repro.uq.mcmc import (
    PooledCovarianceAdapter,
    batched_logpost,
    batched_value_grad_logpost,
    effective_sample_size,
    ensemble_hmc,
    ensemble_mala,
    ensemble_random_walk_metropolis,
)
from repro.uq.mlda import ensemble_mlda


# -- descriptor ---------------------------------------------------------------


def test_capabilities_descriptor_semantics():
    caps = Capabilities(evaluate=True, gradient=True, evaluate_batch=True)
    assert "gradient" in caps and "apply_hessian" not in caps
    assert caps.op_supported("gradient") and not caps.op_supported("apply_jacobian")
    # a native batched variant implies the family
    assert Capabilities(gradient_batch=True).op_supported("gradient")
    assert caps.batched("evaluate") and not caps.batched("gradient")
    sub = Capabilities(evaluate=True)
    assert sub.issubset(caps) and not caps.issubset(sub)
    u = sub.union(Capabilities(gradient=True))
    assert u.evaluate and u.gradient
    i = caps.intersection(Capabilities(evaluate=True, apply_hessian=True))
    assert i.evaluate and not i.gradient
    with pytest.raises(ValueError):
        caps.op_supported("nonsense")


def test_capabilities_wire_roundtrip_and_legacy_alias():
    caps = Capabilities(evaluate=True, gradient_batch=True, apply_hessian=True)
    doc = caps.to_json()
    assert doc["Evaluate"] and doc["GradientBatch"] and doc["ApplyHessian"]
    assert Capabilities.from_json(doc) == caps
    # ModelSupport is a deprecated alias; old five-key docs still parse
    old = {"Evaluate": True, "EvaluateBatch": True}
    ms = ModelSupport.from_json(old)
    assert ms.evaluate and ms.evaluate_batch and not ms.gradient_batch


class _LegacyBatchModel(Model):
    """v1-style model: capability via supports_* overrides only."""

    def get_input_sizes(self, c=None):
        return [2]

    def get_output_sizes(self, c=None):
        return [1]

    def supports_evaluate(self):
        return True

    def supports_evaluate_batch(self):
        return True

    def __call__(self, p, c=None):
        return [[float(np.sum(np.square(p[0])))]]

    def evaluate_batch(self, thetas, config=None):
        return (np.atleast_2d(thetas) ** 2).sum(1, keepdims=True)


def test_base_capabilities_derive_from_legacy_probes():
    caps = model_capabilities(_LegacyBatchModel())
    assert caps.evaluate and caps.evaluate_batch
    assert not caps.op_supported("gradient")
    # implementing a derivative method advertises the family

    class WithGrad(_LegacyBatchModel):
        def gradient(self, out_wrt, in_wrt, parameters, sens, config=None):
            return (2 * np.asarray(parameters[in_wrt]) * sens[0]).tolist()

    assert model_capabilities(WithGrad()).gradient


def test_supports_evaluate_batch_probe_is_deprecated():
    class V2(Model):
        def capabilities(self, config=None):
            return Capabilities(evaluate=True, evaluate_batch=True)

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert V2().supports_evaluate_batch() is True  # shim still answers
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)


def test_bare_call_dispatch_pathway_warns():
    class Duck:  # not a Model: no evaluate_batch at all
        name = "duck"

        def get_input_sizes(self, c=None):
            return [1]

        def __call__(self, p, c=None):
            return [[2.0 * p[0][0]]]

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = ModelBackend(Duck()).evaluate(np.array([[3.0]]), None)
    np.testing.assert_allclose(out, [[6.0]])
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)


# -- FD fallback step sizing --------------------------------------------------


class _ScaledQuadratic(Model):
    """f(theta) = sum((theta/scale)^2) with huge |theta|: an ABSOLUTE FD step
    h ~ 1e-6 differences well below float resolution at theta ~ 1e6 (the old
    bug); the relative step h_i = fd_step * |theta_i| resolves it."""

    SCALE = 1e3

    def get_input_sizes(self, c=None):
        return [2]

    def get_output_sizes(self, c=None):
        return [1]

    def supports_evaluate(self):
        return True

    def evaluate_batch(self, thetas, config=None):
        t = np.atleast_2d(thetas) / self.SCALE
        return (t**2).sum(1, keepdims=True)


def test_fd_gradient_relative_step_scales_with_theta():
    m = _ScaledQuadratic()
    thetas = np.array([[2e6, -3e6], [1e-3, 2e-3]])  # six orders apart
    senss = np.ones((2, 1))
    grads = m._fd_gradient_batch(thetas, senss)
    exact = 2 * thetas / m.SCALE**2
    # large |theta|: h tracks the magnitude, so truncation stays relative
    np.testing.assert_allclose(grads[0], exact[0], rtol=1e-3)
    # below the unit floor the step floors at fd_step (first-order
    # truncation ~ h/2θ) — still the right order, where an absolute step
    # sized for 1e6-scale parameters would be pure noise here
    np.testing.assert_allclose(grads[1], exact[1], rtol=0.1)
    # and the JVP fallback agrees with the VJP fallback through duality:
    # sens . (J v) == (J^T sens) . v
    vecs = np.array([[1.0, 2.0], [0.5, -1.0]])
    jv = m._fd_apply_jacobian_batch(thetas, vecs)
    np.testing.assert_allclose(
        (jv * senss).sum(1), (grads * vecs).sum(1), rtol=0.1
    )


def test_fd_matches_autodiff_on_composite():
    """Satellite regression: the relative-step FD fallback against the AD
    path on CompositeModel's differentiable (smooth-defect) full solve,
    under x64 so float noise does not swamp the small energy sensitivities."""
    from repro.apps.composite import CompositeModel

    with jax.enable_x64(True):
        m = CompositeModel()
        cfg = {"mode": "full", "defect_softness": 1.0}
        thetas = np.array([[77.5, 210.0, 10.0], [70.0, 205.0, 8.0]])
        senss = np.ones((2, 1))
        ad = m.gradient_batch(thetas, senss, cfg)
        m.fd_step = 1e-6  # x64 forward supports a tighter relative step
        fd = m._fd_gradient_batch(thetas, senss, cfg)
    assert np.all(np.isfinite(ad))
    # diameter sensitivity is the dominant, well-conditioned component
    np.testing.assert_allclose(fd[:, 2], ad[:, 2], rtol=5e-2)
    np.testing.assert_allclose(fd, ad, atol=5e-3 * np.abs(ad).max())


# -- JAX-native batched derivative surface ------------------------------------


@pytest.fixture(scope="module")
def jax_model():
    return JAXModel(
        lambda th: jnp.array([jnp.sum(th**2), th[0] - th[1]]), 2, 2
    )


def test_jaxmodel_batched_ops_match_per_point(jax_model):
    m = jax_model
    X = np.array([[1.0, 2.0], [3.0, -1.0], [0.5, 0.25]])
    S = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, -1.0]])
    V = np.array([[1.0, 1.0], [2.0, 0.0], [-1.0, 3.0]])
    gb = m.gradient_batch(X, S)
    for k in range(3):
        pp = np.asarray(m.gradient(0, 0, [X[k].tolist()], S[k].tolist()))
        np.testing.assert_allclose(gb[k], pp, rtol=1e-6)
    jb = m.apply_jacobian_batch(X, V)
    for k in range(3):
        pp = np.asarray(m.apply_jacobian(0, 0, [X[k].tolist()], V[k].tolist()))
        np.testing.assert_allclose(jb[k], pp, rtol=1e-6)
    hb = m.apply_hessian_batch(X, S, V)
    for k in range(3):
        pp = np.asarray(m.apply_hessian(
            0, 0, 0, [X[k].tolist()], S[k].tolist(), V[k].tolist()
        ))
        np.testing.assert_allclose(hb[k], pp, rtol=1e-6)


def test_jaxmodel_fused_value_grad(jax_model):
    m = jax_model
    X = np.array([[1.0, 2.0], [3.0, -1.0]])

    def sens_fn(y):  # traceable: weight the first output only
        return jnp.array([1.0, 0.0]) * (y[0] * 0 + 1.0)

    ys, gs = m.value_and_gradient_batch(X, sens_fn)
    np.testing.assert_allclose(ys, m.evaluate_batch(X), rtol=1e-6)
    np.testing.assert_allclose(gs, 2 * X, rtol=1e-6)  # d(sum sq) = 2 theta

    def np_sens(y):  # NOT traceable (forces numpy conversion of the tracer)
        return np.asarray(y) * 0 + np.array([1.0, 0.0])

    from repro.core.interface import sens_fn_traceable

    assert not sens_fn_traceable(np_sens, 2)  # abstract probe says host-side
    ys2, gs2 = m.value_and_gradient_batch(X, np_sens)  # two-wave fallback
    np.testing.assert_allclose(gs2, gs, rtol=1e-6)


def test_tsunami_gradient_duality():
    """Coarse-level lockstep VJP and JVP agree through the transpose
    identity sens.(J v) == (J^T sens).v — a solver-independent check that
    the adjoint through 2k SWE steps is consistent, not just finite."""
    from repro.apps.tsunami import TsunamiModel

    m = TsunamiModel()
    caps = m.capabilities()
    assert caps.gradient_batch and caps.apply_jacobian_batch
    thetas = np.array([[90.0, 2.5], [120.0, 1.5]])
    senss = np.array([[0.0, 1.0, 0.0, 0.5], [0.0, 0.5, 0.0, 1.0]])
    vecs = np.array([[1.0, 0.2], [0.5, -0.1]])
    g = m.gradient_batch(thetas, senss, {"level": 0})
    jv = m.apply_jacobian_batch(thetas, vecs, {"level": 0})
    assert np.all(np.isfinite(g)) and np.all(np.isfinite(jv))
    np.testing.assert_allclose(
        (jv * senss).sum(1), (g * vecs).sum(1), rtol=5e-2, atol=1e-4
    )
    # amplitude sensitivity of the max-height observables is positive
    assert np.all(g[:, 1] > 0)


def test_tsunami_hessian_duality():
    """Lockstep HVP through the SWE adjoint: symmetric (v2.(H v1) ==
    v1.(H v2) per lane) and consistent with a central difference of the
    sens-contracted gradient — checked on a coarsened hierarchy so the
    second-order scan sweep stays cheap."""
    from repro.apps.tsunami import TsunamiModel

    class SmallTsunami(TsunamiModel):
        N_CELLS = {0: 128, 1: 256}

    m = SmallTsunami()
    assert m.capabilities().apply_hessian_batch
    rng = np.random.default_rng(0)
    thetas = np.array([[90.0, 2.5], [60.0, 1.2]])
    v1 = rng.normal(size=(2, 2))
    v2 = rng.normal(size=(2, 2))
    senss = rng.normal(size=(2, 4))
    h1 = m.apply_hessian_batch(thetas, senss, v1)
    h2 = m.apply_hessian_batch(thetas, senss, v2)
    assert np.all(np.isfinite(h1)) and np.all(np.isfinite(h2))
    # the sens-contracted Hessian is symmetric: bilinear-form duality
    np.testing.assert_allclose(
        np.einsum("ki,ki->k", h1, v2), np.einsum("ki,ki->k", h2, v1),
        rtol=1e-4,
    )
    # central difference of g(theta) = J(theta)^T sens along v1 (eps large
    # enough to clear float32 noise in the solver)
    d = 2

    def sens_grad(tb):
        jv = m.apply_jacobian_batch(
            np.repeat(tb, d, axis=0), np.tile(np.eye(d), (len(tb), 1))
        ).reshape(len(tb), d, 4)
        return np.einsum("km,kdm->kd", senss, jv)

    eps = 1e-2
    fd = (sens_grad(thetas + eps * v1) - sens_grad(thetas - eps * v1)) / (2 * eps)
    np.testing.assert_allclose(h1, fd, rtol=0.1, atol=2e-5)
    # per-point surface delegates to the same batched kernel
    pp = m.apply_hessian(0, 0, 0, [thetas[0].tolist()], senss[0].tolist(),
                         v1[0].tolist())
    np.testing.assert_allclose(np.asarray(pp), h1[0], rtol=1e-5)


# -- HTTP negotiation ---------------------------------------------------------


@pytest.fixture(scope="module")
def grad_server():
    m = JAXModel(lambda th: jnp.array([jnp.sum(th**2), th[0] - th[1]]), 2, 2)
    server, _ = serve_models([m], 45941, background=True)
    yield "http://127.0.0.1:45941"
    server.shutdown()


@pytest.fixture(scope="module")
def eval_only_server():
    server, _ = serve_models([_LegacyBatchModel()], 45942, background=True)
    yield "http://127.0.0.1:45942"
    server.shutdown()


def test_server_advertises_full_capability_set(grad_server):
    hm = HTTPModel(grad_server)
    caps = hm.capabilities()
    assert caps == Capabilities(**{k: True for k in caps.to_json() and {
        "evaluate": 1, "gradient": 1, "apply_jacobian": 1, "apply_hessian": 1,
        "evaluate_batch": 1, "gradient_batch": 1, "apply_jacobian_batch": 1,
        "apply_hessian_batch": 1}})
    # client advertisement ⊆ server advertisement by construction
    assert model_capabilities(hm).issubset(caps)


def test_gradient_batch_one_round_trip(grad_server):
    hm = HTTPModel(grad_server)
    hm.round_trips = 0
    X = np.array([[1.0, 2.0], [3.0, 4.0], [0.5, -0.5]])
    S = np.array([[1.0, 0.0], [1.0, 0.0], [1.0, 0.0]])
    g = hm.gradient_batch(X, S)
    np.testing.assert_allclose(g, 2 * X, rtol=1e-5)
    assert hm.round_trips == 1  # ONE /GradientBatch for the whole wave
    jv = hm.apply_jacobian_batch(X, np.ones((3, 2)))
    np.testing.assert_allclose(jv[:, 0], 2 * X.sum(1), rtol=1e-5)
    assert hm.round_trips == 2


def test_gradient_batch_per_point_fallback(grad_server):
    hm = HTTPModel(grad_server)
    hm._grad_batch_supported = False  # pretend the route predates v2
    hm.round_trips = 0
    X = np.array([[1.0, 2.0], [3.0, 4.0]])
    g = hm.gradient_batch(X, np.array([[1.0, 0.0], [1.0, 0.0]]))
    np.testing.assert_allclose(g, 2 * X, rtol=1e-5)
    assert hm.round_trips == len(X) + 1  # per-point /Gradient + /InputSizes


def test_client_negotiates_subset_against_eval_only_server(eval_only_server):
    hm = HTTPModel(eval_only_server)
    caps = hm.capabilities()
    assert caps.evaluate and caps.evaluate_batch
    assert not caps.op_supported("gradient")
    # per-point /Gradient against an evaluate-only server: typed refusal
    with pytest.raises(RuntimeError, match="UnsupportedFeature"):
        hm.gradient(0, 0, [[1.0, 2.0]], [1.0])
    # batched gradients degrade to the FD fallback riding /EvaluateBatch
    hm.round_trips = 0
    g = hm.gradient_batch(np.array([[1e3, 2e3]]), np.array([[1.0]]))
    np.testing.assert_allclose(g, [[2e3, 4e3]], rtol=1e-3)
    # one failed /GradientBatch probe + one FD evaluate wave
    assert hm.round_trips == 2


def test_apply_hessian_batch_one_round_trip(grad_server):
    """The whole HVP wave rides ONE /ApplyHessianBatch POST. Model
    [sum th^2, th0 - th1]: Hessian of output 0 is 2I, of output 1 is 0, so
    the contracted HVP is 2 * sens[0] * vec."""
    hm = HTTPModel(grad_server)
    assert hm.capabilities().apply_hessian_batch
    hm.round_trips = 0
    X = np.array([[1.0, 2.0], [3.0, -1.0], [0.5, 0.25]])
    S = np.array([[1.0, 0.0], [2.0, 5.0], [-1.0, 3.0]])
    V = np.array([[1.0, 1.0], [2.0, 0.0], [-1.0, 3.0]])
    h = hm.apply_hessian_batch(X, S, V)
    np.testing.assert_allclose(h, 2.0 * S[:, :1] * V, rtol=1e-6)
    assert hm.round_trips == 1


def test_apply_hessian_batch_degrades_to_per_point(grad_server):
    """Against a server whose route predates /ApplyHessianBatch the client
    falls back to per-point /ApplyHessian — explicitly, mirroring the
    gradient ladder (there is NO finite-difference rung for Hessians)."""
    hm = HTTPModel(grad_server)
    hm._hvp_batch_supported = False
    hm.round_trips = 0
    X = np.array([[1.0, 2.0], [3.0, -1.0]])
    S = np.array([[1.0, 0.0], [2.0, 5.0]])
    V = np.array([[1.0, 1.0], [2.0, 0.0]])
    h = hm.apply_hessian_batch(X, S, V)
    np.testing.assert_allclose(h, 2.0 * S[:, :1] * V, rtol=1e-6)
    assert hm.round_trips == len(X) + 1  # per-point route + /InputSizes


def test_apply_hessian_refused_on_evaluate_only_server(eval_only_server):
    """No apply_hessian capability advertised: the client refuses with the
    typed error BEFORE any wire traffic (no probe, no FD fallback)."""
    hm = HTTPModel(eval_only_server)
    assert not hm.capabilities().op_supported("apply_hessian")
    hm.round_trips = 0
    with pytest.raises(UnsupportedCapability, match="apply_hessian"):
        hm.apply_hessian_batch(
            np.ones((2, 2)), np.ones((2, 1)), np.ones((2, 2))
        )
    assert hm.round_trips == 0


def test_health_probe_reports_capabilities(grad_server):
    from repro.core.client import probe_health

    doc = probe_health(grad_server)
    caps = Capabilities.from_json(doc["capabilities"]["forward"])
    assert caps.gradient_batch and caps.evaluate_batch
    assert doc["batch"]["forward"] is True  # legacy key kept


# -- fabric: per-capability cache + routing -----------------------------------


class _CountingGradModel(Model):
    """Quadratic with native batched ops and per-op dispatch counters."""

    def __init__(self, fail_gradient: bool = False):
        super().__init__("forward")
        self.calls = {"evaluate": 0, "gradient": 0, "value_and_gradient": 0}
        self.fail_gradient = fail_gradient

    def get_input_sizes(self, c=None):
        return [2]

    def get_output_sizes(self, c=None):
        return [1]

    def capabilities(self, config=None):
        return Capabilities(
            evaluate=True, evaluate_batch=True, gradient=True, gradient_batch=True
        )

    def evaluate_batch(self, thetas, config=None):
        self.calls["evaluate"] += 1
        return (np.atleast_2d(thetas) ** 2).sum(1, keepdims=True)

    def gradient_batch(self, thetas, senss, config=None):
        if self.fail_gradient:
            raise RuntimeError("adjoint solver down")
        self.calls["gradient"] += 1
        return 2 * np.atleast_2d(thetas) * np.atleast_2d(senss)

    def value_and_gradient_batch(self, thetas, sens_fn, config=None):
        if self.fail_gradient:
            raise RuntimeError("adjoint solver down")
        self.calls["value_and_gradient"] += 1
        ys = (np.atleast_2d(thetas) ** 2).sum(1, keepdims=True)
        senss = np.stack([np.asarray(sens_fn(y), float).ravel() for y in ys])
        return ys, 2 * np.atleast_2d(thetas) * senss


def test_per_capability_cache_isolation():
    m = _CountingGradModel()
    with EvaluationFabric(ModelBackend(m), cache_size=64) as fab:
        X = np.array([[1.0, 2.0]])
        S = np.ones((1, 1))
        fab.evaluate_batch(X)
        assert m.calls["evaluate"] == 1
        # same theta, different capability: MUST NOT serve from the
        # evaluate cache
        g = fab.gradient_batch(X, S)
        np.testing.assert_allclose(g, 2 * X)
        assert m.calls["gradient"] == 1
        # repeat gradient with identical (theta, sens): cache hit
        fab.gradient_batch(X, S)
        assert m.calls["gradient"] == 1
        # different sens: distinct entry, new dispatch
        fab.gradient_batch(X, 2 * S)
        assert m.calls["gradient"] == 2
        # evaluate again: still served from ITS namespace
        fab.evaluate_batch(X)
        assert m.calls["evaluate"] == 1
        t = fab.telemetry()
        assert t["per_capability"]["evaluate"]["waves"] == 1
        assert t["per_capability"]["gradient"]["waves"] == 2
        assert t["per_capability"]["gradient"]["cache_hits"] == 1
        assert "gradient" in t["capabilities"]


def test_fused_wave_is_one_dispatch():
    m = _CountingGradModel()
    with EvaluationFabric(ModelBackend(m), cache_size=64) as fab:
        ys, gs = fab.value_and_gradient_batch(
            np.array([[1.0, 2.0], [3.0, 4.0]]), lambda y: np.ones(1)
        )
        np.testing.assert_allclose(ys.ravel(), [5.0, 25.0])
        np.testing.assert_allclose(gs, [[2.0, 4.0], [6.0, 8.0]])
        assert m.calls["value_and_gradient"] == 1
        assert m.calls["evaluate"] == 0  # truly fused, not two waves
        t = fab.telemetry()
        assert t["per_capability"]["value_and_gradient"]["waves"] == 1


def test_evaluate_only_fabric_refuses_gradient_waves():
    with EvaluationFabric(lambda X: np.atleast_2d(X), cache_size=0) as fab:
        with pytest.raises(UnsupportedCapability):
            fab.gradient_batch(np.ones((2, 2)), np.ones((2, 2)))
        with pytest.raises(UnsupportedCapability):
            fab.value_and_gradient_batch(np.ones((2, 2)), lambda y: y)


def test_router_routes_gradient_waves_only_to_capable_backends():
    m = _CountingGradModel()
    eval_only = CallableBackend(lambda X: (np.atleast_2d(X) ** 2).sum(1, keepdims=True))
    router = FabricRouter([ModelBackend(m), eval_only])
    with EvaluationFabric(router, cache_size=0) as fab:
        rng = np.random.default_rng(0)
        for _ in range(4):  # warm both EWMAs: evaluate waves split
            fab.evaluate_batch(rng.standard_normal((8, 2)))
        X = rng.standard_normal((8, 2))
        g = fab.gradient_batch(X, np.ones((8, 1)))
        np.testing.assert_allclose(g, 2 * X, rtol=1e-6)
        stats = router.stats()
        # evaluate traffic used both backends, gradient only the capable one
        assert stats["per_backend"][1]["points"] > 0
        assert stats["op_waves"]["gradient"] == 1
        assert m.calls["gradient"] >= 1
        assert "gradient" not in Capabilities(
            **{}
        ).names()  # (sanity on empty descriptor)
        assert "gradient" in router.capabilities().names()


def test_router_refuses_to_steal_gradient_wave_onto_evaluate_only():
    """A failing gradient backend must NOT fail over onto an evaluate-only
    survivor: the wave dies with a typed error instead of shattering."""
    sick = _CountingGradModel(fail_gradient=True)
    eval_only = CallableBackend(lambda X: (np.atleast_2d(X) ** 2).sum(1, keepdims=True))
    router = FabricRouter([ModelBackend(sick), eval_only], backoff_s=0.01)
    with EvaluationFabric(router, cache_size=0) as fab:
        with pytest.raises(RuntimeError, match="gradient shard"):
            fab.gradient_batch(np.ones((4, 2)), np.ones((4, 1)))
        assert sick.calls["evaluate"] == 0
    # with a SECOND gradient-capable backend the steal succeeds
    sick2 = _CountingGradModel(fail_gradient=True)
    healthy = _CountingGradModel()
    router2 = FabricRouter([ModelBackend(sick2), ModelBackend(healthy)], backoff_s=0.01)
    with EvaluationFabric(router2, cache_size=0) as fab:
        X = np.ones((4, 2))
        g = fab.gradient_batch(X, np.ones((4, 1)))
        np.testing.assert_allclose(g, 2 * X)
        assert healthy.calls["gradient"] >= 1
    # no gradient-capable backend at all: refused before any dispatch
    router3 = FabricRouter([eval_only])
    with EvaluationFabric(router3, cache_size=0) as fab:
        with pytest.raises(UnsupportedCapability):
            fab.gradient_batch(np.ones((2, 2)), np.ones((2, 1)))


def test_hessian_wave_cache_namespace(jax_model):
    """HVP waves get their own cache namespace keyed on the FULL operand
    triple (theta, sens, vec) — never served from the evaluate or gradient
    namespaces, and distinct probe vectors are distinct entries."""
    with EvaluationFabric(ModelBackend(jax_model), cache_size=64) as fab:
        X = np.array([[1.0, 2.0]])
        S = np.array([[1.0, 0.0]])
        V = np.array([[1.0, 1.0]])
        h = fab.apply_hessian_batch(X, S, V)
        np.testing.assert_allclose(h, 2.0 * S[:, :1] * V, rtol=1e-6)
        t = fab.telemetry()
        assert t["per_capability"]["apply_hessian"]["waves"] == 1
        fab.apply_hessian_batch(X, S, V)  # identical triple: cache hit
        t = fab.telemetry()
        assert t["per_capability"]["apply_hessian"]["waves"] == 1
        assert t["per_capability"]["apply_hessian"]["cache_hits"] == 1
        fab.apply_hessian_batch(X, S, 2.0 * V)  # new vec: real dispatch
        fab.apply_hessian_batch(X, 2.0 * S, V)  # new sens: real dispatch
        t = fab.telemetry()
        assert t["per_capability"]["apply_hessian"]["waves"] == 3
        # same theta under evaluate: ITS namespace, not the HVP entries
        fab.evaluate_batch(X)
        assert fab.telemetry()["per_capability"]["evaluate"]["waves"] == 1


def test_router_routes_hessian_waves_only_to_capable_backends(jax_model):
    def np_forward(X):
        X = np.atleast_2d(X)
        return np.stack([(X**2).sum(1), X[:, 0] - X[:, 1]], axis=1)

    eval_only = CallableBackend(np_forward)
    router = FabricRouter([ModelBackend(jax_model), eval_only])
    with EvaluationFabric(router, cache_size=0) as fab:
        rng = np.random.default_rng(0)
        for _ in range(4):  # warm both EWMAs on evaluate traffic
            fab.evaluate_batch(rng.standard_normal((8, 2)))
        X = rng.standard_normal((6, 2))
        S = rng.standard_normal((6, 2))
        V = rng.standard_normal((6, 2))
        h = fab.apply_hessian_batch(X, S, V)
        np.testing.assert_allclose(h, 2.0 * S[:, :1] * V, rtol=1e-5)
        stats = router.stats()
        assert stats["per_backend"][1]["points"] > 0  # evaluate split
        assert stats["op_waves"]["apply_hessian"] == 1
        assert "apply_hessian" in router.capabilities().names()
    # no hessian-capable backend at all: refused before any dispatch
    with EvaluationFabric(FabricRouter([eval_only]), cache_size=0) as fab:
        with pytest.raises(UnsupportedCapability):
            fab.apply_hessian_batch(
                np.ones((2, 2)), np.ones((2, 2)), np.ones((2, 2))
            )


# -- gradient-based lockstep samplers ----------------------------------------


MU = np.array([1.0, -2.0])
SIG = np.array([[2.0, 0.8], [0.8, 1.0]])
SIG_INV = np.linalg.inv(SIG)


class _IdentityVG:
    """Identity model: J = I, so grad logpost == grad loglik — exact."""

    def value_and_gradient_batch(self, thetas, sens_fn, config=None):
        ys = np.atleast_2d(np.asarray(thetas, float))
        return ys, np.stack([np.asarray(sens_fn(y), float) for y in ys])


def _gauss_vg():
    return batched_value_grad_logpost(
        _IdentityVG(),
        lambda y: float(-0.5 * (y - MU) @ SIG_INV @ (y - MU)),
        lambda y: -SIG_INV @ (np.asarray(y) - MU),
    )


def test_ensemble_mala_recovers_gaussian():
    vg = _gauss_vg()
    rng = np.random.default_rng(1)
    x0s = rng.standard_normal((16, 2))
    res = ensemble_mala(vg, x0s, 2000, 0.8, rng, precond=SIG, adapt_steps=200)
    S = res.samples[:, 400:, :].reshape(-1, 2)
    assert 0.4 < res.accept_rate < 0.8  # adapted toward 0.574
    np.testing.assert_allclose(S.mean(0), MU, atol=0.1)
    np.testing.assert_allclose(np.cov(S.T), SIG, atol=0.25)
    assert res.n_grad_waves == res.n_waves == 2001
    assert res.final_step_size is not None


def test_ensemble_hmc_recovers_gaussian():
    vg = _gauss_vg()
    rng = np.random.default_rng(2)
    x0s = rng.standard_normal((16, 2))
    res = ensemble_hmc(vg, x0s, 500, 0.9, 5, rng, precond=SIG, adapt_steps=100)
    S = res.samples[:, 100:, :].reshape(-1, 2)
    assert res.accept_rate > 0.6
    np.testing.assert_allclose(S.mean(0), MU, atol=0.12)
    np.testing.assert_allclose(np.cov(S.T), SIG, atol=0.3)
    assert res.n_waves == 500 * 5 + 1  # one fused wave per leapfrog substep


def test_mala_beats_rwm_ess_per_wave_on_gaussian():
    """The economics the gradient surface buys: at the SAME wave count,
    drift-informed proposals decorrelate faster than blind ones."""
    rng = np.random.default_rng(3)
    x0s = MU + rng.standard_normal((16, 2)) @ np.linalg.cholesky(SIG).T
    n = 400
    res_m = ensemble_mala(_gauss_vg(), x0s, n, 1.4, np.random.default_rng(4), precond=SIG)
    lp = batched_logpost(
        lambda X: np.atleast_2d(X),
        lambda y: float(-0.5 * (y - MU) @ SIG_INV @ (y - MU)),
    )
    res_w = ensemble_random_walk_metropolis(
        lp, x0s, n, (2.38**2 / 2) * SIG, np.random.default_rng(4)
    )
    ess_m = sum(effective_sample_size(res_m.samples[k, :, 0]) for k in range(16))
    ess_w = sum(effective_sample_size(res_w.samples[k, :, 0]) for k in range(16))
    assert res_m.n_waves == res_w.n_waves
    assert ess_m > 1.5 * ess_w  # comfortably above parity (typically ~3x)


def test_batched_value_grad_logpost_masks_prior():
    calls = {"points": 0}

    class VG(_IdentityVG):
        def value_and_gradient_batch(self, thetas, sens_fn, config=None):
            calls["points"] += len(np.atleast_2d(thetas))
            return super().value_and_gradient_batch(thetas, sens_fn, config)

    vg = batched_value_grad_logpost(
        VG(),
        lambda y: float(-0.5 * y @ y),
        lambda y: -np.asarray(y),
        logprior=lambda t: 0.0 if abs(t[0]) < 1.0 else -np.inf,
        grad_logprior=lambda t: np.zeros(2),
    )
    thetas = np.array([[0.5, 0.0], [5.0, 0.0], [-0.25, 1.0]])
    lps, glps = vg(thetas)
    assert np.isfinite(lps[0]) and np.isfinite(lps[2])
    assert lps[1] == -np.inf and np.all(glps[1] == 0)
    assert calls["points"] == 2  # masked point never reached the model
    assert vg.points_evaluated == 2 and vg.waves == 1
    vg.reset()
    assert vg.waves == 0


def test_fabric_fused_waves_visible_per_capability():
    """End to end: MALA through a fabric over an AD model — every sampler
    step is ONE value_and_gradient wave in the fabric telemetry."""
    m = JAXModel(lambda th: th * 1.0, 2, 2)  # identity, J = I

    def grad_loglik(y):
        return -(y - jnp.asarray(MU, y.dtype)) @ jnp.asarray(SIG_INV, y.dtype)

    with EvaluationFabric(ModelBackend(m), cache_size=0) as fab:
        vg = batched_value_grad_logpost(
            fab,
            lambda y: float(-0.5 * (y - MU) @ SIG_INV @ (y - MU)),
            grad_loglik,
        )
        rng = np.random.default_rng(5)
        res = ensemble_mala(vg, rng.standard_normal((8, 2)), 20, 1.0, rng, precond=SIG)
        t = fab.telemetry()
    assert t["per_capability"]["value_and_gradient"]["waves"] == 21
    assert t["per_capability"]["value_and_gradient"]["points"] == 21 * 8
    assert "evaluate" not in t["per_capability"] or (
        t["per_capability"]["evaluate"]["waves"] == 0
    )
    assert res.n_waves == 21


# -- pooled Haario adaptation -------------------------------------------------


def test_pooled_covariance_adapter_matches_numpy():
    rng = np.random.default_rng(6)
    blocks = [rng.standard_normal((8, 3)) @ np.diag([1.0, 2.0, 0.5]) for _ in range(40)]
    ad = PooledCovarianceAdapter(3)
    for b in blocks:
        ad.update(b)
    allx = np.concatenate(blocks, 0)
    np.testing.assert_allclose(ad.mean, allx.mean(0), rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(ad.cov(), np.cov(allx.T), rtol=1e-10, atol=1e-12)


def test_adaptive_ensemble_rwm_learns_pooled_covariance():
    target_cov = np.array([[4.0, 1.5], [1.5, 1.0]])
    tinv = np.linalg.inv(target_cov)
    lp = batched_logpost(
        lambda X: np.atleast_2d(X), lambda y: float(-0.5 * y @ tinv @ y)
    )
    rng = np.random.default_rng(7)
    x0s = rng.standard_normal((16, 2))
    # start from a hopelessly isotropic tiny proposal
    res = ensemble_random_walk_metropolis(
        lp, x0s, 1200, 0.01 * np.eye(2), rng,
        adaptive=True, adapt_start=30,
    )
    assert res.proposal_cov is not None
    # adapted proposal ~ (2.38^2/d) * target covariance, correlation learned
    corr = res.proposal_cov[0, 1] / np.sqrt(
        res.proposal_cov[0, 0] * res.proposal_cov[1, 1]
    )
    true_corr = 1.5 / 2.0
    assert abs(corr - true_corr) < 0.2
    ratio = res.proposal_cov[0, 0] / res.proposal_cov[1, 1]
    assert 2.5 < ratio < 6.5  # anisotropy (true 4.0) learned through pooling
    assert 0.1 < res.accept_rate < 0.6


def _coarse_vg(X):
    """Batched value+grad of the biased coarse posterior N(-0.5, 2I)."""
    X = np.atleast_2d(np.asarray(X, float))
    return -0.25 * ((X + 0.5) ** 2).sum(1), -0.5 * (X + 0.5)


def test_ensemble_mlda_mala_coarse_targets_fine_posterior():
    """Gradient-informed coarse subchains leave the DA correction exact:
    with a BIASED coarse level (N(-0.5, 2I)) under MALA, the chain still
    targets the fine posterior N(1, I)."""
    from _stat_harness import assert_moments

    rng = np.random.default_rng(9)
    res = ensemble_mlda(
        [lambda X: _coarse_vg(X)[0],
         lambda X: -0.5 * ((np.atleast_2d(X) - 1.0) ** 2).sum(1)],
        rng.standard_normal((12, 2)) + 1.0, 250, [4], 0.7 * np.eye(2), rng,
        coarse_sampler="mala", coarse_value_grad=_coarse_vg, mala_step=0.8,
    )
    assert_moments(res.samples, 1.0, 1.0, z=6.0, min_ess=80,
                   label="mala-coarse mlda")
    assert res.accept_rates[0] > 0.3  # the MALA subchain actually moves
    assert np.all(np.isfinite(res.samples))


def test_ensemble_mlda_mala_builds_value_grad_from_fabric():
    """With `fabric=` + `grad_loglik=` the coarse value-and-gradient view
    is assembled automatically and every MALA subchain step is ONE fused
    wave in the fabric telemetry."""
    m = JAXModel(lambda th: th * 1.0, 2, 2)  # identity: J = I
    fab = EvaluationFabric(ModelBackend(m), cache_size=0)
    try:
        rng = np.random.default_rng(10)
        res = ensemble_mlda(
            None, rng.standard_normal((8, 2)), 120, [3], np.eye(2), rng,
            fabric=fab,
            loglik=lambda y: -0.5 * float(np.sum(np.square(y))),
            grad_loglik=lambda y: -y,
            level_configs=[{}, {}],
            coarse_sampler="mala", mala_step=0.8,
        )
        t = fab.telemetry()
    finally:
        fab.shutdown()
    assert t["per_capability"]["value_and_gradient"]["waves"] > 0
    assert res.accept_rates[1] > 0.9  # identical levels: DA nearly always accepts
    assert np.all(np.isfinite(res.samples))


def test_ensemble_mlda_mala_validation():
    rng = np.random.default_rng(0)
    x0s = np.zeros((4, 2))
    two = [lambda X: _coarse_vg(X)[0], lambda X: _coarse_vg(X)[0]]
    with pytest.raises(ValueError, match="coarse_sampler"):
        ensemble_mlda(two, x0s, 5, [2], np.eye(2), rng, coarse_sampler="hmc")
    with pytest.raises(ValueError, match="incompatible"):
        ensemble_mlda(two, x0s, 5, [2], np.eye(2), rng,
                      coarse_sampler="mala", coarse_value_grad=_coarse_vg,
                      adaptive=True)
    with pytest.raises(ValueError, match="coarse_value_grad"):
        ensemble_mlda(two, x0s, 5, [2], np.eye(2), rng, coarse_sampler="mala")
    with pytest.raises(ValueError, match="two levels"):
        ensemble_mlda([two[0]], x0s, 5, [], np.eye(2), rng,
                      coarse_sampler="mala", coarse_value_grad=_coarse_vg)


def test_ensemble_mlda_adaptive_proposal():
    def model(thetas, config):
        shift = -0.5 if (config or {}).get("level") == 0 else 0.0
        return ((np.atleast_2d(thetas) - shift) ** 2).sum(1, keepdims=True)

    fab = EvaluationFabric(model, cache_size=2048)
    try:
        res = ensemble_mlda(
            None, np.zeros((8, 2)), 120, [3], 0.05 * np.eye(2),
            np.random.default_rng(8),
            fabric=fab,
            loglik=lambda out: -0.5 * float(out[0]),
            level_configs=[{"level": 0}, {"level": 1}],
            adaptive=True, adapt_start=40,
        )
    finally:
        fab.shutdown()
    assert res.proposal_cov is not None
    assert np.all(np.isfinite(res.samples))
    # the tiny initial proposal was widened toward the posterior scale
    assert res.proposal_cov[0, 0] > 0.05 * 0.05
    assert res.accept_rates[0] > 0.05
