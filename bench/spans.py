"""The program's own span and counter registry (``repro.obs``), as readers see it.

A reader of a span metric uses only the records in which nothing was traced
or compiled, which leaves the warm-up compiles out.  A program without the
registry, or one that recorded nothing, gives ``None``: the metric is then
left out of the result line.
"""
from __future__ import annotations


def snapshot():
    """``repro.obs.snapshot()``, or None where the program has no registry or
    it holds nothing."""
    try:
        from repro import obs
    except ImportError:
        return None
    snap = obs.snapshot()
    if not (snap["spans"] or snap["counters"] or snap["traces"]):
        return None
    return snap


def steady(snap, name: str) -> list:
    """The records of span ``name`` in which nothing traced or compiled."""
    return [s for s in snap["spans"]
            if s["name"] == name and not s["traces"] and not s["compiles"]]


def mean_ms(records):
    return 1e-6 * sum(r["duration_ns"] for r in records) / len(records) if records else None


def tick_waits(snap):
    """Steady ``serve.wait`` records split by whether their tick (the
    ``serve.tick`` of the same beat) proposed: (holding, proposing)."""
    proposed = {t["beat"]: t["attrs"].get("proposed") for t in steady(snap, "serve.tick")}
    hold, prop = [], []
    for w in steady(snap, "serve.wait"):
        p = proposed.get(w["beat"])
        if p is not None:
            (prop if p else hold).append(w)
    return hold, prop
