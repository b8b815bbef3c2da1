"""Traces of the program's entry points beyond the first of each, over the
whole run: a re-trace inside the window would compile there.  Counted by
``repro.obs`` from JAX's trace events, per function name."""
from bench.spans import snapshot

ENTRY_POINTS = ("push", "tick", "observe_dag", "propose_dag")


def read(ctx):
    snap = snapshot()
    if snap is None:
        return None
    traces = snap["traces"]
    seen = [n for n in ENTRY_POINTS if n in traces]
    return sum(traces[n] - 1 for n in seen) if seen else None
