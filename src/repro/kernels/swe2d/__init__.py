from repro.kernels.swe2d.ops import sea_path, sea_solve
from repro.kernels.swe2d.swe2d import face_flux, sea_kernel, update, velocities

__all__ = ["sea_path", "sea_solve", "sea_kernel", "face_flux", "update", "velocities"]
