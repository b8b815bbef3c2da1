"""Device milliseconds per beat spent in the posterior-grid kernel launched
on the active set's M-worker slab: the ``posterior_grid_fleet_pallas``
events of the device trace, read as ``grid_kernel_ms`` reads them."""
from bench.metrics.grid_kernel_ms import read  # noqa: F401
