"""Operations and HBM bytes that one posterior-grid evaluation needs, from shapes.

The evaluation is the expanded form of ``docs/math.md``: for every worker
``k``, grid point ``g`` and observation ``n`` one pow-table entry
``pg = exp(g log f)``, its square and reciprocal square, and three
multiply-accumulates,

    S_a(g) = A0 - 2 mu <pg, u> + mu^2 <pg^2, v>,   S_b(g) = <1/pg^2, w>,

so nine operations a cell (an ``exp``, a reciprocal and a multiply count
one each).  Per worker and observation, the shared vectors ``log f, u, v,
w`` take eight more; per worker and grid point, the priors and the Jacobian
term eight more.  Bytes: ``t, f`` and the mask read once (float32), nine
scalars a worker, the grid, and the (K, 2, G) float32 output written once.

The count follows the algorithm and the shapes, never the kernel's tiling
or padding: K workers (S*K for a workflow), G grid points, N observations
actually drained.  A kernel that pads or re-reads does more than this.
"""
from __future__ import annotations

OPS_PER_CELL = 9
OPS_PER_OBS = 8
OPS_PER_GRID_POINT = 8
F32 = 4


def grid_posterior_work(workers: int, grid: int, obs: int) -> tuple:
    """(operations, bytes) of one evaluation over the whole fleet."""
    k, g, n = int(workers), int(grid), int(obs)
    ops = k * (g * n * OPS_PER_CELL + n * OPS_PER_OBS + g * OPS_PER_GRID_POINT)
    nbytes = F32 * (3 * k * n + 9 * k + g + 2 * k * g)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak_flops: float,
                  peak_bytes: float) -> tuple:
    """The roofline's least time and the bound that sets it."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bytes
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
