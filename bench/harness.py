"""One run of one cell: set-up, the measured window, the check, the result line.

The harness knows no configuration, traffic mix or metric by name.  A cell
names its configuration and traffic in ``BENCHMARK.json``; the configuration
names its driver (``bench/drivers/<driver>.py``); each per-layer metric is a
reader in ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib
import math
import shutil
import sys
import tempfile
import time

from . import common
from .generator import sub_seeds
from .trace_reduce import breakdown, reduce

SEED_NAMES = ("layout", "noise", "program", "check", "quality")
# A --trace 1 run traces the start of its window: at least TRACE_SECONDS and
# at least one publication.
TRACE_SECONDS = 2.0
MAX_FLUSH_BEATS = 64


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation of the sorted values."""
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def publish_latencies(rows, pubs):
    """Seconds from each row's push to the first publication at or after the
    beat that absorbed it, and the number of rows that no publication
    followed.  ``rows``: (beat, push time); ``pubs``: (beat, time)."""
    out = []
    j = 0
    pubs = sorted(pubs)
    rows = sorted(rows)
    for n, (beat, t) in enumerate(rows):
        while j < len(pubs) and pubs[j][0] < beat:
            j += 1
        if j == len(pubs):
            return out, len(rows) - n
        out.append(pubs[j][1] - t)
    return out, 0


def run_cell(cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, t_start: float, devices, per_layer=(), e2e=(),
             log=print) -> dict:
    """Drive one cell and return the result line as a dict."""
    import jax

    seeds = sub_seeds(seed, SEED_NAMES)
    driver_mod = importlib.import_module(f"bench.drivers.{cfg['driver']}")
    drv = driver_mod.Driver(cfg, traffic, seeds, trace)
    for _ in range(int(cfg["warm_beats"])):
        drv.beat("warm", -1)
    drv.ready()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    rows, pubs = [], []
    traced_beats = traced_pubs = 0
    tracing = trace
    t0 = now = time.perf_counter()
    longest = (0.0, -1)  # the longest beat of the window, (seconds, index)
    index = 0
    while True:
        with common.span("beat", tracing):
            pushed, published = drv.beat("window", index)
        rows.extend((index, t) for t in pushed)
        if published is not None:
            pubs.append((index, published))
        last, now = now, time.perf_counter()
        longest = max(longest, (now - last, index))
        index += 1
        if tracing:
            traced_beats += 1
            traced_pubs += published is not None
            if now - t0 >= min(TRACE_SECONDS, seconds) and traced_pubs:
                jax.profiler.stop_trace()
                tracing = False
        if now - t0 >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
    window_s = now - t0
    window_beats = index
    # Every row of the window waits for a publication: beat on until one comes.
    flush = 0
    while (not pubs or pubs[-1][0] < window_beats - 1) and flush < MAX_FLUSH_BEATS:
        _, published = drv.beat("flush", index)
        if published is not None:
            pubs.append((index, published))
        index += 1
        flush += 1
    drv.ready()

    stats = [d.memory_stats() or {} for d in drv.memory_devices()]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    latencies, unpublished = publish_latencies(rows, pubs)
    latencies = latencies or [math.inf]
    values = {
        "rows_per_s": drv.rows_per_beat * window_beats / window_s,
        "publish_p95_ms": 1e3 * quantile(latencies, 0.95),
        "setup_s": setup_s,
    }
    log(f"window {window_s:.3f} s, {window_beats} beats, {len(rows)} rows, "
        f"{sum(1 for b, _ in pubs if b < window_beats)} publications, "
        f"{flush} flush beats; publish p50 {1e3 * quantile(latencies, 0.5):.1f} ms "
        f"over {len(latencies)} rows; longest beat {1e3 * longest[0]:.1f} ms "
        f"(beat {longest[1]}; mean {1e3 * window_s / window_beats:.1f} ms)")

    drv.collect()
    values["makespan_ratio"] = drv.makespan_ratio()
    checks = dict(drv.checks(), rows_unpublished=unpublished)
    limits = dict(cfg["limits"], rows_unpublished=0)
    correct = all(limits.get(k) is not None and checks[k] <= limits[k]
                  for k in checks)

    kind = jax.devices()[0].device_kind
    device = {"platform": jax.devices()[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(rows),
              "failed": int(checks.get("ring_dropped", 0)) + unpublished}
    if trace:
        red = reduce(trace_dir, devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = common.peaks()["devices"]
        if kind not in peaks:
            raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
        ctx = dict(trace=red, beats=traced_beats, pubs=traced_pubs,
                   push_s=getattr(drv, "push_s", None), kernel=drv.kernel_shape(),
                   peak=peaks[kind])
        metrics = {}
        for m in per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for note in ctx.get("notes", []):
            log(note)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = breakdown(red)
    else:
        metrics = {m["name"]: {"value": _finite(values[m["name"]]), "unit": m["unit"]}
                   for m in e2e}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": _finite(v), "limit": limits.get(k)}
                        for k, v in checks.items()}
    return result


def _finite(v):
    """A number JSON can carry; None for one that is not finite."""
    return v if math.isfinite(v) else None


def cell_metrics(bench: dict, cell_name: str):
    """The end-to-end and per-layer metrics that ``BENCHMARK.json`` gives a
    cell: those whose ``workloads`` list it, or that have no such list."""
    listed = lambda m: cell_name in m.get("workloads", [cell_name])
    return ([m for m in bench["end_to_end"] if listed(m)],
            [m for m in bench["per_layer"] if listed(m)])


def print_checks(result: dict, out=sys.stderr) -> None:
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}", file=out)
    out.flush()
