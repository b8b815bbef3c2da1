"""Device milliseconds per beat spent in the posterior-grid kernel.

The kernel is the Mosaic kernel of ``repro.kernels.posterior_grid``: in the
device trace, the ``tpu_custom_call`` instruction named after its launcher,
``%posterior_grid_fleet_pallas.<n>``.
"""
NAME = "posterior_grid_fleet_pallas"


def kernel_seconds(red):
    return sum(d for text, _, d in red["events"]
               if text.lstrip("%").startswith(NAME) and "tpu_custom_call" in text)


def read(ctx):
    red = ctx.get("trace")
    if not red or not ctx.get("beats"):
        return None
    s = kernel_seconds(red)
    return 1e3 * s / ctx["beats"] if s > 0 else None
