"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per trace.  Its device planes
(``/device:TPU:<n>``) hold the operations that ran on each chip; its host
plane (``/host:CPU``) holds the benchmark's own spans (``bench.<name>``), on
the same clock.  This module reduces the trace to:

  * ``busy_s``: the union of the intervals in which an operation ran on the
    device, inside the traced window, averaged over the chips used;
  * ``ops``: per device operation, by its HLO instruction name (``%name.N``
    with the number dropped), its summed seconds and its count; operations
    that only contain others (``while``, ``conditional``, ``call``) are left
    out here, though they count towards ``busy_s``;
  * ``events``: every device operation as (HLO text, start_s, seconds), for
    readers that pick operations by name;
  * ``modules``: every program run as (module name, start_s, seconds);
  * ``idle_gaps``: the device's idle intervals, each named by the innermost
    benchmark span that covers its middle (``host`` where none does).

The window is the interval from the first to the last benchmark span.
"""
from __future__ import annotations

import glob
from collections import defaultdict

SPAN_PREFIX = "bench."


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_CONTAINERS = (" while(", " conditional(", " call(")


def op_name(text: str) -> str:
    """``%posterior_grid_fleet_pallas.8 = (...) custom-call(...)`` ->
    ``posterior_grid_fleet_pallas``: the instruction name, number dropped."""
    head = text.split(" = ", 1)[0].lstrip("%")
    stem, _, num = head.rpartition(".")
    return stem if stem and num.isdigit() else head


def reduce(log_dir: str, devices: int = 1) -> dict:
    """Reduce the trace the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise RuntimeError(f"no trace under {log_dir}")
    return reduce_planes(ProfileData.from_file(sorted(paths)[-1]).planes, devices)


def reduce_planes(planes, devices: int = 1) -> dict:
    """Reduce planes that have ``name`` and ``lines``; lines ``name`` and
    ``events``; events ``name``, ``start_ns``, ``duration_ns`` and ``stats``."""
    spans = []
    device_planes = []
    modules = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name[len(SPAN_PREFIX):], s,
                                      s + ev.duration_ns * 1e-9))
    if not spans:
        raise RuntimeError("the trace holds no benchmark span")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    device_planes = sorted(device_planes, key=lambda p: p.name)[:devices]

    events, ops = [], defaultdict(lambda: [0.0, 0])
    busy_total, busy0 = 0.0, []
    for i, plane in enumerate(device_planes):
        iv = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                d = ev.duration_ns * 1e-9
                if s + d < lo or s > hi:
                    continue
                iv.append((max(s, lo), min(s + d, hi)))
                if not any(c in ev.name for c in _CONTAINERS):
                    name = op_name(ev.name)
                    ops[name][0] += d
                    ops[name][1] += 1
                if i == 0:
                    events.append((ev.name, s, d))
        for line in plane.lines:
            if line.name == "XLA Modules" and i == 0:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if lo <= s <= hi:
                        modules.append((ev.name, s, ev.duration_ns * 1e-9))
        u = _union(iv)
        busy_total += sum(e - s for s, e in u)
        if i == 0:
            busy0 = u

    gaps = []
    prev = lo
    for s, e in busy0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named_gaps = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(b - a, n) for n, a, b in spans if a <= mid <= b]
        named_gaps.append((min(cover)[1] if cover else "host", e - s))

    n_dev = max(len(device_planes), 1)
    return dict(
        window_s=hi - lo,
        busy_s=busy_total / n_dev,
        ops={k: (v[0], v[1]) for k, v in ops.items()},
        events=events,
        modules=modules,
        idle_gaps=named_gaps,
    )


def breakdown(red: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1][0])[:top]
    gaps = sorted(red["idle_gaps"], key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, s] for n, (s, _) in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
