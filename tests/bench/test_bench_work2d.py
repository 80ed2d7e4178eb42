"""The 2-D cell's fixed parts: the algorithmic count against the reference
step, its readers, the configuration against the program's grids, and the
bfloat16 control."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core
from bench import compare, harness, peaks, work, work2d
from bench import program_spans as ps
from repro.core.tracing import Record
from test_bench_units import _count_flops

CELL = "mlda2d-k128"
MS = 1_000_000


@pytest.mark.parametrize("level", [0, 1])
def test_flop_count_is_the_reference_steps(level):
    cell = harness.load_cell(CELL)
    ref = harness.reference_module(cell)
    step, _ = ref.make_solver(cell.config, level, jnp.float32)
    gr = ref.grid(cell.config, level)
    h = jnp.full((gr["ny"], gr["nx"]), 100.0, jnp.float32)
    carry = (h, h * 0.0, h * 0.0, jnp.zeros(2, jnp.float32), jnp.zeros(2, jnp.int32))
    jaxpr = jax.make_jaxpr(step)(carry, jnp.int32(0)).jaxpr
    per_cell = _count_flops(jaxpr) / (gr["nx"] * gr["ny"])
    # the faces along each axis number one fewer than the cells, and the
    # buoys' observables add a few scalars: under half an op per cell
    assert work2d.FLOPS_PER_CELL == 143
    assert abs(per_cell - work2d.FLOPS_PER_CELL) < 0.5, per_cell


def test_configuration_is_the_programs_2d_sea():
    from repro.apps import tsunami as ts

    cfg = harness.load_cell(CELL).config
    ref = harness.reference_module(harness.load_cell(CELL))
    for level, lv in enumerate(cfg["levels"]):
        grid = ts.TsunamiModel.GRIDS_2D[level]
        assert (lv["nx"], lv["ny"], lv["smoothed"]) == (grid.nx, grid.ny, grid.smoothed)
        assert cfg["t_end_s"] == grid.t_end and lv["dof"] == 3 * grid.cells
        assert lv["steps"] == grid.steps == ref.grid(cfg, level)["n_steps"]
        assert ref.grid(cfg, level)["buoys"] == list(ts._buoy_cells(grid))
        np.testing.assert_allclose(ref.bathymetry(cfg, grid.nx, grid.ny, grid.smoothed),
                                   ts._sea_bathymetry(grid.nx, grid.ny, grid.smoothed),
                                   rtol=1e-6, atol=1e-3)
    assert [tuple(b) for b in cfg["buoys_km"]] == list(ts.BUOYS_2D_KM)
    assert cfg["source_amplitude_m"] == ts.SOURCE_AMP
    assert cfg["source_width_km"] * 1e3 == ts.SOURCE_WIDTH
    assert cfg["bathymetry"]["bend_km"] == ts.COAST_BEND_KM


def test_work_counts_solved_lanes_only():
    one = work2d.chunk_flops({"lanes": 1, "padded": 0, "cells": 240 * 240, "steps": 1042})
    assert one == 240 * 240 * 1042 * 143
    assert work2d.chunk_flops({"lanes": 16, "padded": 3, "cells": 240 * 240,
                               "steps": 1042}) == 13 * one
    # compulsory bytes are tiny: every solve is bound by compute
    attrs = {"lanes": 16, "padded": 0, "cells": 480 * 480, "steps": 2085}
    pk = peaks.peak("TPU v5 lite")
    flops, nbytes = work2d.chunk_flops(attrs), work2d.chunk_bytes(attrs)
    assert nbytes == 4 * (16 * 6 + 480 * 480)
    assert work.least_time_s(flops, nbytes, pk) == flops / pk["flops_per_s"]


def _rec(id_, name, start_ms, end_ms, parent=None, **attrs):
    return Record(name, "tsunami-batch_0", start_ms * MS, end_ms * MS, attrs, id_, parent)


#: one traced second: two chunks of a 2-D wave, one of them with a padded
#: lane, and a chunk of a program that names no grid
CHUNKS = [
    _rec(1, "model.chunk", 0, 400, level=0, lanes=16, padded=0, cells=57600, steps=1042),
    _rec(2, "device.wait", 10, 390, 1),
    _rec(3, "model.chunk", 400, 800, level=0, lanes=16, padded=1, cells=57600, steps=1042),
    _rec(4, "model.chunk", 800, 900, level=1, lanes=4, padded=0),
]


def _ctx():
    return harness.LayerContext(cell=None, window_s=1.0, busy_s={0: 0.5}, programs={0: 3},
                                spans=[], peak=peaks.peak("TPU v5 lite"), modules=[],
                                offset_ns=0)


@pytest.mark.parametrize("metric,want", [
    # 31 solved lanes x 57,600 cells x 1,042 steps x 143 FLOPs = 2.66e11
    ("swe2d_mfu_pct.mlda", 100.0 * 31 * 57600 * 1042 * 143 / 197e12),
    ("swe2d_roofline.mlda", 100.0 * 31 * 57600 * 1042 * 143 / 197e12 / 0.5),
])
def test_readers(metric, want, monkeypatch):
    monkeypatch.setattr(ps, "records", lambda: CHUNKS)
    assert harness.reader(metric).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["swe2d_roofline.mlda", "swe2d_mfu_pct.mlda"])
def test_readers_report_nothing_without_the_spans_they_read(metric, monkeypatch):
    """No chunk that names its grid's work (a program before the chunks
    carried `cells` and `steps`), or a program without a recorder: None,
    and nothing raised."""
    monkeypatch.setattr(ps, "records", lambda: [])
    assert harness.reader(metric).read(_ctx()) is None
    monkeypatch.setattr(ps, "records", lambda: CHUNKS[3:])
    assert harness.reader(metric).read(_ctx()) is None
    monkeypatch.undo()
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert ps.records() is None
    assert harness.reader(metric).read(_ctx()) is None


def test_new_metrics_are_declared_for_the_2d_cell():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in ("swe2d_roofline.mlda", "swe2d_mfu_pct.mlda"):
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["moves"] == "samples_per_s.mlda"


@pytest.mark.parametrize("level", [0, 1])
def test_observable_gap_rejects_the_control(level):
    """The reference in bfloat16, one step below the float32 the
    configuration states, fails the cell's limit; the program passes it.
    At small grids of the cell's sea (the chip's readings at full size are
    in PERF.md): bfloat16 resolves a 4 km depth to 16 m, so no wave of a
    few metres survives it."""
    from repro.apps.tsunami import Grid, _solve_batch_2d

    cell = harness.load_cell(CELL)
    cfg = dict(cell.config, levels=[{"nx": 32, "ny": 32, "smoothed": True},
                                    {"nx": 40, "ny": 40, "smoothed": False}])
    ref = harness.reference_module(cell)
    thetas = harness.prior_points(np.random.default_rng(2**36 + 11), 4, cfg["prior_box"])
    lv = cfg["levels"][level]
    grid = Grid(lv["nx"], lv["ny"], lv["smoothed"], cfg["t_end_s"])
    prog = np.asarray(_solve_batch_2d(jnp.asarray(thetas, jnp.float32), grid), float)
    f32 = ref.observables_block(cfg, level, thetas)
    bf16 = ref.observables_block(cfg, level, thetas, "bfloat16")
    limit = cell.traffic["limits"][f"obs_gap_l{level}_sd"]
    sd = cfg["noise_sd"]
    assert compare.obs_gap_sd(bf16, f32, sd) > limit
    assert compare.obs_gap_sd(prog, f32, sd) <= limit
