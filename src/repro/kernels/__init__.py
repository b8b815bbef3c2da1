"""Pallas TPU kernels for the framework's compute hot spots.

  * posterior_grid — the paper's O(K*G*N) exponent-posterior numerical
    integration (Eqs 10/11/16-18), the Gibbs sweep's dominant cost at
    production telemetry volumes; one fused launch evaluates every worker in
    the fleet and both exponents (alpha and beta) from a single pass over
    the telemetry.
  * decode_attention — flash-decode GQA attention over deep KV caches
    (the decode_32k serving cells).
  * lru_scan — blocked linear-recurrence scan (RG-LRU / SSM core; keeps the
    running state VMEM-resident so HBM sees each element exactly once).

``ops`` holds the jit'd public wrappers (interpret=True on CPU), ``ref`` the
pure-jnp oracles the kernels are validated against.
"""
from . import ops, ref
from .decode_attention import decode_attention_pallas
from .lru_scan import lru_scan_pallas
from .posterior_grid import posterior_grid_fleet_pallas

__all__ = [
    "ops",
    "ref",
    "decode_attention_pallas",
    "lru_scan_pallas",
    "posterior_grid_fleet_pallas",
]
