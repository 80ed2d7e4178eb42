"""Spans inside the program (`repro.core.tracing`): off by default and then
free of side effects, parents across threads, compile spans, the profiler's
clock, the layer boundaries the spans sit at, and `TsunamiModel`'s
chunk-padding counters."""
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import tsunami
from repro.apps.tsunami import TsunamiModel
from repro.core import tracing
from repro.core.fabric import EvaluationFabric, ModelBackend
from repro.core.interface import JAXModel
from repro.uq.fused import fused_ensemble_rwm, gaussian_target
from repro.uq.gp import GP
from repro.uq.mlda import ensemble_mlda


@pytest.fixture()
def recorded():
    """Recording on, with an empty record list; both restored after."""
    tracing.clear()
    with tracing.recording():
        yield
    tracing.clear()


def _by_id(recs):
    return {r.id: r for r in recs}


def _root(rec, by_id):
    while rec.parent is not None:
        rec = by_id[rec.parent]
    return rec


def test_off_records_nothing_and_enters_nothing(monkeypatch):
    calls = []

    class Annotation:
        def __init__(self, *a, **k):
            calls.append("annotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append("sync"))
    tracing.clear()
    assert not tracing.is_recording()
    sp = tracing.span("fabric.evaluate", op="evaluate")
    assert sp is tracing.NULL_SPAN and tracing.span("other") is sp
    with sp as inner:
        inner.set(points=3)
        assert tracing.current() is None
    x = jnp.ones(3)
    assert tracing.wait(x) is x
    assert calls == [] and tracing.records() == []


def test_nesting_sets_parent_and_attrs(recorded):
    with tracing.span("outer", a=1) as outer:
        assert tracing.current() == outer.id
        with tracing.span("inner") as inner:
            inner.set(points=5)
        time.sleep(0.001)
    assert tracing.current() is None
    recs = {r.name: r for r in tracing.records()}
    assert recs["inner"].parent == recs["outer"].id and recs["outer"].parent is None
    assert recs["inner"].attrs == {"points": 5} and recs["outer"].attrs == {"a": 1}
    assert recs["outer"].start_ns <= recs["inner"].start_ns <= recs["inner"].end_ns \
        <= recs["outer"].end_ns
    assert recs["outer"].seconds >= 1e-3
    lo, hi = recs["inner"].start_ns, recs["inner"].end_ns
    assert [r.name for r in tracing.records(lo, hi)] == ["inner"]


def test_spans_from_many_threads_are_all_kept(recorded):
    """More threads than cores, switching often: no record is lost, ids are
    unique and every inner span names its own thread's outer span."""
    n_threads, n_spans = 24, 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_spans):
                with tracing.span("outer"):
                    with tracing.span("inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    recs = tracing.records()
    assert len(recs) == 2 * n_threads * n_spans
    by_id = _by_id(recs)
    assert len(by_id) == len(recs)
    inner = [r for r in recs if r.name == "inner"]
    assert all(by_id[r.parent].name == "outer" and by_id[r.parent].thread == r.thread
               for r in inner)


def test_recording_nests_and_stops():
    with tracing.recording():
        with tracing.recording():
            pass
        assert tracing.is_recording()
    assert not tracing.is_recording()


def test_compile_span_under_the_open_span(recorded):
    scale = time.perf_counter_ns() % 1000 + 0.5  # a program no test compiled
    with tracing.span("step") as step:
        jax.block_until_ready(jax.jit(lambda x: x * scale + 1.0)(jnp.ones(7)))
    compiles = [r for r in tracing.records() if r.name == "jax.compile"]
    assert compiles and all(r.parent == step.id for r in compiles)
    assert all(step.id < r.id for r in compiles)


def test_chunk_spans_on_executor_threads_under_the_dispatch(recorded, monkeypatch):
    # two chunks (4 + 1 lanes), so the executor runs them
    monkeypatch.setattr(tsunami.os, "cpu_count", lambda: 2)
    fabric = EvaluationFabric(ModelBackend(TsunamiModel()), cache_size=0)
    try:
        thetas = np.column_stack([np.linspace(80.0, 100.0, 5), np.full(5, 2.0)])
        fabric.evaluate_batch(thetas, {"level": 0})
    finally:
        fabric.shutdown()
    recs = tracing.records()
    by_id = _by_id(recs)
    (dispatch,) = [r for r in recs if r.name == "backend.dispatch"]
    chunks = [r for r in recs if r.name == "model.chunk"]
    assert len(chunks) == 2
    assert all(c.parent == dispatch.id for c in chunks)
    assert all(c.thread.startswith("tsunami-batch") for c in chunks)
    assert sorted((c.attrs["lanes"], c.attrs["padded"]) for c in chunks) == [(4, 0), (4, 3)]
    # a chunk's work: level 0 is 512 cells for 2,224 steps
    assert all((c.attrs["cells"], c.attrs["steps"]) == (512, 2224) for c in chunks)
    assert dispatch.attrs == {"op": "evaluate", "lanes": 5}
    waits = [r for r in recs if r.name == "device.wait"]
    assert sorted(w.parent for w in waits) == sorted(c.id for c in chunks)
    # every span of the call shares its root: the fabric call
    roots = {_root(r, by_id).name for r in recs if r.name != "jax.compile"}
    assert roots == {"fabric.evaluate"}
    (fab,) = [r for r in recs if r.name == "fabric.evaluate"]
    assert fab.attrs == {"op": "evaluate", "points": 5, "hits": 0}


def test_fabric_spans_count_points_sent_and_rows_served(recorded):
    fabric = EvaluationFabric(ModelBackend(JAXModel(lambda th: th ** 2, 1, 1)))
    try:
        fabric.evaluate_batch([[1.0], [2.0]])
        fabric.evaluate_batch([[1.0], [3.0], [3.0]])
        fabric.gradient_batch([[1.0], [2.0]], [[1.0], [1.0]])
        fabric.value_and_gradient_batch([[4.0]], lambda y: y)
    finally:
        fabric.shutdown()
    recs = tracing.records()
    spans = [(r.name, r.attrs) for r in recs if r.name.startswith("fabric.")]
    assert spans == [
        ("fabric.evaluate", {"op": "evaluate", "points": 2, "hits": 0}),
        ("fabric.evaluate", {"op": "evaluate", "points": 1, "hits": 2}),
        ("fabric.gradient", {"op": "gradient", "points": 2, "hits": 0}),
        ("fabric.value_and_gradient", {"op": "value_and_gradient", "points": 1, "hits": 0}),
    ]
    by_id = _by_id(recs)
    ops = [(by_id[r.parent].name, r.attrs["op"]) for r in recs if r.name == "backend.dispatch"]
    assert ops == [("fabric.evaluate", "evaluate"), ("fabric.evaluate", "evaluate"),
                   ("fabric.gradient", "gradient"),
                   ("fabric.value_and_gradient", "value_and_gradient")]


def test_gp_predict_and_fused_sampler_spans(recorded):
    X = np.linspace(0.0, 1.0, 6)[:, None]
    gp = GP.from_params(X, np.sin(3 * X[:, 0]), np.array([np.log(0.3), 0.0, -9.0, 0.0]))
    gp.predict(np.array([[0.25], [0.5], [0.75]]))
    lp = gaussian_target(np.zeros(2), np.eye(2))
    x0s = np.random.default_rng(0).normal(size=(4, 2))
    fused_ensemble_rwm(lp, x0s, 6, 0.5 * np.eye(2), jax.random.key(1), fused_steps=3)
    recs = tracing.records()
    by_id = _by_id(recs)
    (pred,) = [r for r in recs if r.name == "gp.predict"]
    assert pred.attrs == {"points": 3, "padded": 5}
    children = {r.name for r in recs if r.parent == pred.id}
    assert "device.wait" in children and children <= {"device.wait", "jax.compile"}
    (sampler,) = [r for r in recs if r.name == "sampler.fused"]
    assert sampler.attrs == {"chains": 4, "blocks": 2}
    blocks = [r for r in recs if r.name == "fused.block"]
    assert len(blocks) == 2 and all(b.parent == sampler.id and b.attrs == {"steps": 3}
                                    for b in blocks)
    waits = [r for r in recs if r.name == "device.wait" and r.parent in {b.id for b in blocks}]
    assert len(waits) == 2
    assert all(_root(w, by_id).name == "sampler.fused" for w in waits)


def test_mlda_step_spans(recorded):
    def level(shift):
        def logpost(thetas):
            return -0.5 * np.sum((np.atleast_2d(thetas) - shift) ** 2, axis=1)
        return logpost

    x0s = np.random.default_rng(1).normal(size=(3, 2))
    ensemble_mlda([level(0.0), level(0.5)], x0s, 3, [2], np.eye(2),
                  np.random.default_rng(2))
    steps = [r for r in tracing.records() if r.name == "sampler.mlda_step"]
    assert [r.attrs["step"] for r in steps] == [0, 1, 2]
    assert all(r.parent is None for r in steps)


def test_spans_land_on_the_profilers_clock(tmp_path):
    """A span's record, shifted by the anchor offset the benchmark harness
    uses, lies within 50 us of its `repro.` event in the trace: the two are
    on one clock. A profiler trace turns recording on by itself."""
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert tracing.is_recording()
        with jax.profiler.TraceAnnotation("test.anchor"):
            anchor_ns = time.perf_counter_ns()
        with tracing.span("clock_probe", n=1):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.is_recording()
    (rec,) = [r for r in tracing.records() if r.name == "clock_probe"]
    tracing.clear()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name.split("#")[0], (e.start_ns, e.duration_ns))
    offset = events["test.anchor"][0] - anchor_ns
    start, duration = events["repro.clock_probe"]
    assert abs(rec.start_ns + offset - start) < 50e3
    assert abs(rec.end_ns + offset - (start + duration)) < 50e3


@pytest.mark.parametrize("cores", [1, 2, 8])
def test_tsunami_padding_reaches_fabric_telemetry(monkeypatch, cores):
    """5 points pad to 8 lanes at any core count (4 + 1->4, or 5->8): the
    model counts the 3 padded lanes and the fabric's `padding_waste` sees
    them; the dispatcher's own `padded` stays 0."""
    monkeypatch.setattr(tsunami.os, "cpu_count", lambda: cores)
    model = TsunamiModel()
    fabric = EvaluationFabric(ModelBackend(model), cache_size=0)
    try:
        thetas = np.column_stack([np.linspace(80.0, 100.0, 5), np.full(5, 2.0)])
        fabric.evaluate_batch(thetas, {"level": 0})
        tel = fabric.telemetry()
    finally:
        fabric.shutdown()
    assert tel["backend"]["padded"] == 0 and tel["backend"]["model_padded"] == 3
    assert tel["padding_waste"] == pytest.approx(3 / 8)
    assert "busy_fraction_hint" not in tel
    counts = model.chunk_stats[0]
    assert counts["lanes"] == 8 and counts["padded"] == 3
    assert counts["programs"] == (1 if cores == 1 else 2)
    assert model.chunk_stats[1] == {"lanes": 0, "padded": 0, "programs": 0}
