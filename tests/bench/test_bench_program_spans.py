"""The readers of the program's own spans (`bench/program_spans.py`), on a
hand-built traced window of one second with known spans: one MLDA step and
one fused call. Without a recorder in the program every reader reports
nothing."""
import sys

import pytest

import repro.core
from bench import harness
from bench import program_spans as ps
from bench import trace as tr
from repro.core.tracing import Record

MS = 1_000_000


def _rec(id_, name, start_ms, end_ms, parent=None, thread="campaign", **attrs):
    return Record(name, thread, start_ms * MS, end_ms * MS, attrs, id_, parent)


MLDA_STEP = [
    _rec(1, "sampler.mlda_step", 0, 1000, step=0),
    _rec(2, "gp.predict", 100, 300, 1, points=128),
    _rec(3, "device.wait", 150, 200, 2),
    _rec(4, "fabric.evaluate", 400, 800, 1, op="evaluate", points=8, hits=0),
    _rec(5, "backend.dispatch", 450, 750, 4, op="evaluate", lanes=7),
    _rec(6, "model.chunk", 460, 740, 5, "tsunami-batch_0", level=1, lanes=4, padded=1),
    _rec(7, "device.wait", 470, 730, 6, "tsunami-batch_0"),
    _rec(8, "model.chunk", 460, 700, 5, "tsunami-batch_1", level=1, lanes=4, padded=0),
    _rec(9, "device.wait", 480, 690, 8, "tsunami-batch_1"),
    _rec(10, "jax.compile", 850, 900, 1, fun_name="jit(_solve_batch)"),
]

FUSED_CALL = [
    _rec(1, "sampler.fused", 0, 400, chains=128, blocks=2),
    _rec(2, "fused.block", 50, 200, 1, steps=25),
    _rec(3, "device.wait", 60, 190, 2),
    _rec(4, "fused.block", 200, 380, 1, steps=25),
    _rec(5, "device.wait", 210, 370, 4),
]


def _ctx():
    return harness.LayerContext(cell=None, window_s=1.0, busy_s={0: 0.5}, programs={0: 3},
                                spans=[], peak=None, modules=[], offset_ns=0)


def _read(metric, recs, monkeypatch):
    monkeypatch.setattr(ps, "records", lambda: recs)
    return harness.reader(metric).read(_ctx())


@pytest.mark.parametrize("metric,want", [
    ("gp_predict_pct.mlda", 20.0),  # 200 ms of gp.predict in 1 s
    ("sampler_self_pct.mlda", 35.0),  # 1000 - 200 gp - 400 fabric - 50 compile
    ("fabric_host_ms.mlda", 100.0),  # 400 ms call - 300 ms backend dispatch
    ("padded_lane_pct.mlda", 12.5),  # 1 of 8 lanes
    ("device_wait_pct.mlda", 31.0),  # 50 + union(260, 210 inside it) ms
])
def test_mlda_readers(metric, want, monkeypatch):
    assert _read(metric, MLDA_STEP, monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("device_wait_pct.fused", 29.0),  # 130 + 160 ms
    ("block_host_ms.fused", 55.0),  # (400 - 290) ms over 2 blocks
])
def test_fused_readers(metric, want, monkeypatch):
    assert _read(metric, FUSED_CALL, monkeypatch) == pytest.approx(want)


NEW_METRICS = ["gp_predict_pct.mlda", "sampler_self_pct.mlda", "fabric_host_ms.mlda",
               "padded_lane_pct.mlda", "device_wait_pct.mlda", "device_wait_pct.fused",
               "block_host_ms.fused"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_report_nothing_without_the_spans_they_read(metric, monkeypatch):
    """No spans of their kind, or a program without a recorder (where
    `repro.core.tracing` cannot be imported): None, and nothing raised."""
    assert _read(metric, [], monkeypatch) is None
    monkeypatch.undo()
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert ps.records() is None
    assert harness.reader(metric).read(_ctx()) is None


def test_new_metrics_are_declared_for_the_cells_they_read():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        group = name.split(".")[1]
        assert declared[name]["workloads"] == {"mlda": ["mlda-k128"],
                                               "fused": ["fused-rwm-k128"]}[group]
        assert declared[name]["moves"] == f"samples_per_s.{group}"


def test_attribute_labels_a_gap_with_an_innermost_program_span():
    """Program spans shifted onto the trace's clock label the idle gaps they
    hold, beside the benchmark's own spans: the innermost span open at a
    gap's middle names it, whichever side recorded it."""
    offset = 5 * MS
    bench_spans = [("gp", 90 * MS + offset, 310 * MS + offset)]
    program = [(r.name, r.start_ns + offset, r.end_ns + offset) for r in MLDA_STEP]
    gaps = [(110 * MS + offset, 140 * MS + offset),  # in gp.predict, before its wait
            (820 * MS + offset, 840 * MS + offset),  # in the step, outside its children
            (1200 * MS + offset, 1210 * MS + offset)]  # after the step
    labels = [label for label, _ in tr.attribute(gaps, bench_spans + program)]
    assert labels == ["gp.predict", "sampler.mlda_step", "outside spans"]
