from repro.kernels.swe1d.ops import transect_path, transect_solve
from repro.kernels.swe1d.swe1d import (
    face_flux,
    transect_kernel,
    update,
    velocity,
    well_balanced,
)

__all__ = ["transect_path", "transect_solve", "transect_kernel", "face_flux", "update",
           "velocity", "well_balanced"]
