"""The `mlda2d-k128` cell through the harness at a size a test can hold: its
result line (`test_bench_faults_mlda2d.py` plants faults under the same
cut). The cut is this file's own, since `conftest.py` cuts the cells it
knows: small grids and a short simulated time for both levels, few chains,
short subchains; the comparison and its limits are the cell's."""
import json
import time

import jax

from bench import harness

#: the cell's cut: both levels on a 96 x 96 grid (on coarser ones the waves
#: reach the buoys near the arrival threshold, the arrival jumps with the
#: source, and the GP screen fails), 1,500 s simulated (the last arrival
#: from the prior box is at about 1,170 s), the observations made by the
#: cut's fine level as the cell's are by its own
TINY_LEVELS = [{"nx": 96, "ny": 96, "smoothed": True}, {"nx": 96, "ny": 96, "smoothed": False}]
TINY_TRAFFIC = {"chains": 4, "subsampling": [4, 2], "gp_train_points": 32,
                "gp_fit_iters": 100, "proposal_sd": [8.0, 8.0],
                "check_rows": {"0": 4, "1": 2}, "fine_steps_per_call": 2}


def tiny_cell():
    cell = harness.load_cell("mlda2d-k128")
    cell.config.update(levels=TINY_LEVELS, t_end_s=1500.0)
    ref = harness.reference_module(cell)
    truth = [cell.config["true_theta"]]
    cell.config["data_clean"] = ref.observables_block(cell.config, 1, truth)[0].tolist()
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def run_tiny(seed: int = 2**33 + 7) -> dict:
    return harness.execute(tiny_cell(), seed, 1.0, False, jax.devices()[:1],
                           time.perf_counter_ns())


def test_driver_result_line():
    res = run_tiny()
    json.dumps(res)  # one JSON object
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s.mlda", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(res["checks"]) == {"obs_gap_l0_sd", "obs_gap_l1_sd", "frozen_steps",
                                  "untested_share"}
