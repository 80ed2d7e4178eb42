"""Algorithmic work of the 2-D sea's solves, counted from the work a chunk
names: the `model.chunk` span's `lanes` less its `padded`, `cells` and
`steps`.

As in `bench/work.py`, the count belongs to the yardstick, not to the
program: however a later change computes a 2-D solve, its work is what the
plain step in `configs/tsunami2d_swe_ref.py` needs, per cell, per lane, per
time step. Padded lanes do not count. Bytes are the compulsory ones only:
theta in, observables out and the bathymetry once per program; the state
never has to leave the chip, so a solve is always bound by compute.
"""
from __future__ import annotations

from bench.work import BYTES_PER_VALUE, OBS_VALUES, THETA_VALUES

#: floating-point operations of one 2-D step, per cell and lane, by hand
#: from the reference step (a compare, select, max, abs, sqrt or divide
#: counts as one; x**2 as one multiply, x**4 as two); the faces along x and
#: along y each count once per cell
FLOPS_PER_CELL_STEP = {
    "velocity": 11,  # h**4, max(h, h_dry)**4, +, sqrt, sqrt2*h, /, then u, v
    "reconstruction": 2 * 6,  # hl, hr: (h + b) - b_star, max(., 0)
    "mass_fluxes": 2 * 2,  # ml = hl*ul, mr = hr*ur
    "wave_speed": 2 * 10,  # |u| + sqrt(g*h) per side, max, half*speed
    "flux_h": 2 * 5,  # half*(ml + mr) - damp*(hr - hl)
    "flux_normal": 2 * 13,  # ml*ul + (g/2)*hl*hl per side, +, half*, mr - ml, *, -
    "flux_tangential": 2 * 9,  # half*(ml*vl + mr*vr) - damp*(hr*vr - hl*vl)
    "pressure_corrections": 2 * 10,  # f_n + (g/2)*(h**2 - hl**2), each side
    "divergence": 2 * 3,  # f[i] - f[i-1] of mass, normal and tangential flux
    "update": 16,  # h - lx*dh_x - ly*dh_y, max 0; h > h_dry; hu, hv alike, select
}
FLOPS_PER_CELL = sum(FLOPS_PER_CELL_STEP.values())  # 143


def chunk_flops(attrs: dict) -> float:
    """Operations of the solves of one `model.chunk` span."""
    lanes = attrs["lanes"] - attrs.get("padded", 0)
    return float(lanes) * attrs["cells"] * attrs["steps"] * FLOPS_PER_CELL


def chunk_bytes(attrs: dict) -> float:
    """Compulsory bytes of one `model.chunk` span: theta in and observables
    out of its solved lanes, and the bathymetry of its program."""
    lanes = attrs["lanes"] - attrs.get("padded", 0)
    return float(BYTES_PER_VALUE) * (lanes * (THETA_VALUES + OBS_VALUES) + attrs["cells"])


def chunk_work(recs) -> tuple[float, float]:
    """(flops, bytes) of the chunk spans among `recs` that name their grid's
    work; (0, 0) where none does."""
    chunks = [r for r in recs if r.name == "model.chunk" and "cells" in r.attrs]
    return (sum(chunk_flops(r.attrs) for r in chunks),
            sum(chunk_bytes(r.attrs) for r in chunks))
