from repro.kernels.swe.ref import (
    swe2d_simulate_ref,
    swe2d_step_ref,
    swe_simulate_ref,
    swe_step_ref,
)

__all__ = ["swe_step_ref", "swe_simulate_ref", "swe2d_step_ref", "swe2d_simulate_ref"]
