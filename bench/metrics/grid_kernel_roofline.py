"""The posterior-grid kernel's share of its roofline, in %.

The least time the chip could take for the kernel's work in the traced beats,
the larger of operations over peak FLOP/s and bytes over peak HBM bytes/s
(``bench/workcount.py``, from shapes alone, and ``bench/peaks.json``), over
the kernel's device time in those beats.
"""
from bench.metrics.grid_kernel_ms import kernel_seconds
from bench.workcount import grid_posterior_work, least_seconds


def read(ctx):
    red = ctx.get("trace")
    if not red or not ctx.get("beats"):
        return None
    spent = kernel_seconds(red)
    if spent <= 0:
        return None
    k = ctx["kernel"]
    ops, nbytes = grid_posterior_work(k["workers"], k["grid"], k["obs"])
    launches = k["launches"] * ctx["beats"]
    least, bound = least_seconds(ops * launches, nbytes * launches,
                                 ctx["peak"]["flops_per_s"],
                                 ctx["peak"]["hbm_bytes_per_s"])
    ctx.setdefault("notes", []).append(f"grid_kernel_roofline is {bound}-bound")
    return 100.0 * least / spent
