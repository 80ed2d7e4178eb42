from repro.kernels.swe.ops import swe_step
from repro.kernels.swe.ref import swe2d_simulate_ref, swe2d_step_ref, swe_step_ref
from repro.kernels.swe.swe import swe_step_kernel

__all__ = ["swe_step", "swe_step_ref", "swe_step_kernel", "swe2d_step_ref", "swe2d_simulate_ref"]
