"""The 2-D MLDA cell's `correct` comes out false with the timed path broken
underneath: chains that never move, half the chains left where they are, a
wrong answer from the 2-D solver."""
import pytest

import faults
from test_bench_mlda2d import run_tiny


def answer_altered_2d(monkeypatch):
    """The 2-D solver's buoy-1 height is off by 0.5 m (10 noise sd)."""
    import repro.apps.tsunami as ts

    orig = ts._solve_batch_2d

    def altered(thetas, grid):
        return orig(thetas, grid).at[:, 1].add(0.5)

    monkeypatch.setattr(ts, "_solve_batch_2d", altered)


@pytest.mark.parametrize("fault", [faults.mlda_state_unchanged, faults.mlda_half_chains,
                                   answer_altered_2d])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run_tiny()
    assert res["correct"] is False, res["checks"]
