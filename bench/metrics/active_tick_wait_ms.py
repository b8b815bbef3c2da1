"""Host milliseconds a holding active-set tick waits on the device: the mean
``serve.wait`` span over the ticks that did not propose and whose
``serve.tick`` says it gave fewer than all K workers a full grid evaluation
(its ``refreshed`` attribute), so the selection, the slab's sweeps and
kernel, the surrogate sweeps, the merge and the gate, without the solve.
K is the cell's ``Driver.kernel_shape()["fleet"]``.  A program whose
ticks carry no ``refreshed`` gives ``None``."""
from bench.spans import mean_ms, snapshot, steady


def read(ctx):
    snap = snapshot()
    fleet = (ctx.get("kernel") or {}).get("fleet")
    if snap is None or fleet is None:
        return None
    active = {t["beat"] for t in steady(snap, "serve.tick")
              if not t["attrs"].get("proposed")
              and 0 < t["attrs"].get("refreshed", fleet) < fleet}
    return mean_ms([w for w in steady(snap, "serve.wait") if w["beat"] in active])
