"""Host milliseconds a holding tick waits on the device: the mean
``serve.wait`` span (the tick's one host sync) over the ticks that did not
propose, so the tick program's Gibbs sweeps, kernel and gate without the
solve."""
from bench.spans import mean_ms, snapshot, tick_waits


def read(ctx):
    snap = snapshot()
    return None if snap is None else mean_ms(tick_waits(snap)[0])
