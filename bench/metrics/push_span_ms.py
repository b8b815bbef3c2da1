"""Host milliseconds per ``ServiceLoop.push``, read inside the program: the
mean ``serve.push`` span of ``repro.obs`` over the steady records."""
from bench.spans import mean_ms, snapshot, steady


def read(ctx):
    snap = snapshot()
    return None if snap is None else mean_ms(steady(snap, "serve.push"))
