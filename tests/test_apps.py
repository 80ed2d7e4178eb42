"""Application model tests (the paper's three applications)."""
import numpy as np
import pytest

from repro.apps.composite import CompositeModel
from repro.apps.l2sea import FROUDE_RANGE, L2SeaModel, make_inputs
from repro.apps.tsunami import T_END, TsunamiModel, _solve_batch


@pytest.fixture(scope="module")
def l2sea():
    return L2SeaModel()


def test_l2sea_interface(l2sea):
    assert l2sea.get_input_sizes() == [16]
    assert l2sea.get_output_sizes() == [1]
    out = l2sea([list(make_inputs(np.array([[0.3, -6.0]]))[0])])
    assert out[0][0] > 0


def test_l2sea_resistance_grows_with_froude(l2sea):
    rts = [
        l2sea([list(make_inputs(np.array([[f, -6.16]]))[0])])[0][0]
        for f in np.linspace(*FROUDE_RANGE, 6)
    ]
    assert rts[-1] > 2 * rts[0]  # steep growth with speed


def test_l2sea_deeper_draft_more_resistance(l2sea):
    shallow = l2sea([list(make_inputs(np.array([[0.33, -5.6]]))[0])])[0][0]
    deep = l2sea([list(make_inputs(np.array([[0.33, -6.7]]))[0])])[0][0]
    assert deep > shallow


def test_l2sea_fidelity_bias(l2sea):
    x = list(make_inputs(np.array([[0.33, -6.16]]))[0])
    coarse = l2sea([x], {"fidelity": 7})[0][0]
    fine = l2sea([x], {"fidelity": 1})[0][0]
    assert coarse > fine  # coarser grid over-predicts


@pytest.fixture(scope="module")
def composite():
    return CompositeModel()


def test_composite_rom_matches_full(composite):
    for th in ([77.5, 210.0, 10.0], [78.0, 180.0, 30.0]):
        e_full = composite([th], {"mode": "full"})[0][0]
        e_rom = composite([th], {"mode": "rom"})[0][0]
        assert abs(e_rom - e_full) / e_full < 5e-3, th


def test_composite_defect_reduces_energy(composite):
    pristine = composite([[0.0, 0.0, 0.001]], {"mode": "full"})[0][0]
    damaged = composite([[77.5, 210.0, 60.0]], {"mode": "full"})[0][0]
    assert damaged < pristine


def test_composite_online_locality(composite):
    _, info = composite.rom.online(np.array([77.5, 210.0, 10.0]))
    assert 1 <= len(info["updated_subdomains"]) <= 8  # paper: "one to ~eight"


@pytest.fixture(scope="module")
def tsunami():
    return TsunamiModel()


def test_tsunami_still_water(tsunami):
    (a1, h1, a2, h2), = np.asarray(_solve_batch(np.array([[80.0, 0.0]], np.float32), 512, True))
    assert max(abs(h1), abs(h2)) < 1e-2
    assert a1 == a2 == np.float32(T_END / 60.0)  # the wave never arrives


def test_tsunami_arrival_ordering(tsunami):
    near = tsunami([[120.0, 2.0]], {"level": 0})[0]
    far = tsunami([[40.0, 2.0]], {"level": 0})[0]
    assert near[0] < far[0]  # buoy 1 arrival
    assert near[2] < far[2]  # buoy 2 arrival
    assert all(np.isfinite(near)) and all(np.isfinite(far))


def test_tsunami_levels_close_but_not_equal(tsunami):
    o0 = np.asarray(tsunami([[80.0, 2.0]], {"level": 0})[0])
    o1 = np.asarray(tsunami([[80.0, 2.0]], {"level": 1})[0])
    assert not np.allclose(o0, o1)  # different fidelity
    assert np.all(np.abs(o0 - o1) / (np.abs(o1) + 1e-6) < 0.5)  # but correlated
