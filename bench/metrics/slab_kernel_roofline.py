"""The slab kernel's share of its roofline, in %: ``grid_kernel_roofline``'s
reading, where the cell's ``Driver.kernel_shape()`` prices ``n_iters``
evaluations a beat over the M workers of the active slab, not the fleet."""
from bench.metrics.grid_kernel_roofline import read  # noqa: F401
