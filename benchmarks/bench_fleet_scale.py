"""Fleet-scale serving benchmark: dense grid vs compressed active-set path.

The dense estimation program materializes a (K, 2, G) exponent log-posterior
per Gibbs sweep — the memory/bandwidth wall that caps practical fleets near
K = 10^4 (~400 MB of transient grid at K = 10^5, G = 512).  The compressed
path (``ServeConfig.active_size`` + ``async_propose``) runs the full grid
program only for the top-M active workers (young / surprising / anomalous /
stale — ``core.compress.select_active``), advances the rest through the
moment-matched Beta surrogate, and dispatches the simplex solve OFF the tick
path, publishing on completion.

Per fleet size this module records:

  * **propose-tick p50/p99** for each side — the latency the serving beat
    actually sits behind (every tick proposes: staleness=1, gate held);
  * an interleaved min-time A/B row (``time_pair_min``) with the
    dense/compressed speedup — the acceptance target is >= 5x at K = 10^5;
  * **posterior-state bytes**: the analytic per-sweep grid working set
    (``compress.compression_report``, >= 10x smaller at K = 10^5) plus the
    measured live-array footprint and process peak-RSS high-water mark;
  * a **reader-latency** row: ``fractions()`` p50 while a fleet-sized solve
    is in flight — the published split is a host-buffer read, independent
    of solve time (the non-blocking-tick acceptance check);
  * the O(K log K) water-fill quantization at K = 10^5 (the host rounding
    that was O(K^2 log K) before the vectorized shed/top-up).

``smoke_main`` is the CI entry: reduced grid sizes (G = 64/32 — the guard
that keeps a CPU-only runner in minutes) and few samples; ``main`` widens
the grids and sample counts.  Rows land in ``experiments/BENCH_8.json``.
"""
from __future__ import annotations

import resource
import time

import numpy as np

from benchmarks.common import emit, time_pair_min
from repro import sched, serve
from repro.core import compress

_RING = 8  # telemetry rows buffered per drain


def _pctiles(samples_us):
    s = sorted(samples_us)
    return s[len(s) // 2], s[-1] if len(s) < 100 else s[int(len(s) * 0.99)]


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _live_mb() -> float:
    import jax

    return sum(a.nbytes for a in jax.live_arrays()) / 1e6


def _make_loop(k: int, grid: int, *, active=None, async_p=False):
    mu = np.linspace(0.5, 2.0, k)
    config = serve.ServeConfig(
        sched=sched.SchedulerConfig(
            n_iters=2, grid_size=grid, num_points=128, opt_steps=20,
            mu_guess=float(mu.mean()),
        ),
        capacity=_RING,
        # Every data tick proposes: the gate never fires, staleness always
        # does — clean propose-tick samples on both sides.
        drift_threshold=1e9,
        max_staleness=1,
        active_size=active,
        async_propose=async_p,
    )
    loop = serve.ServiceLoop(k, config=config, seed=1)
    fracs = ((1.0 / mu) / (1.0 / mu).sum()).astype(np.float32)
    rng = np.random.default_rng(0)

    def step_times():
        return (
            fracs**0.9 * mu + fracs**0.8 * 0.05 * mu * rng.standard_normal(k)
        ).astype(np.float32)

    return loop, fracs, step_times


def _drive(loop, fracs, step_times, n_ticks: int, warmup: int = 1):
    """Push one ring of telemetry + tick, ``n_ticks`` timed rounds."""
    samples = []
    info = None
    for d in range(warmup + n_ticks):
        for _ in range(_RING):
            loop.push(fracs, step_times())
        t0 = time.perf_counter()
        info = loop.tick()
        dt = (time.perf_counter() - t0) * 1e6
        if d >= warmup:
            samples.append(dt)
    assert info is not None and bool(info.drained)
    return samples


def _fleet_case(
    k: int, grid: int, active: int, *, dense_ticks: int, comp_ticks: int,
    ab_rounds: int = 0,
) -> None:
    label = f"k{k}_g{grid}"

    # -- compressed first: the dense side then owns the RSS high-water mark
    comp, fracs, step = _make_loop(k, grid, active=active, async_p=True)
    cs = _drive(comp, fracs, step, comp_ticks)
    p50c, p99c = _pctiles(cs)
    emit(
        f"fleet_propose_tick_compressed_{label}", p50c,
        f"p99={p99c:.0f}us n={len(cs)} active M={active} async solve "
        f"off-path; live={_live_mb():.0f}MB rss_peak={_peak_rss_mb():.0f}MB",
    )

    # -- reader latency while a fleet-sized solve is in flight -------------
    # The tick above dispatched a solve; time the published-split read now.
    in_flight = comp._pending is not None
    reads = []
    for _ in range(200):
        t0 = time.perf_counter()
        fr = comp.fractions()
        reads.append((time.perf_counter() - t0) * 1e6)
    assert fr.shape == (k,)
    p50r, p99r = _pctiles(reads)
    emit(
        f"fleet_fractions_read_{label}", p50r,
        f"p99={p99r:.1f}us host buffer read, solve_in_flight={in_flight} "
        "(reader never blocks on the solve)",
    )
    while comp.poll() is False and comp._pending is not None:
        time.sleep(0.01)
    del comp

    rss_before_dense = _peak_rss_mb()
    dense, fracs, step = _make_loop(k, grid)
    ds = _drive(dense, fracs, step, dense_ticks)
    p50d, p99d = _pctiles(ds)
    emit(
        f"fleet_propose_tick_dense_{label}", p50d,
        f"p99={p99d:.0f}us n={len(ds)} full (K,2,G) grid + in-tick solve; "
        f"live={_live_mb():.0f}MB rss_peak={_peak_rss_mb():.0f}MB "
        f"(+{_peak_rss_mb() - rss_before_dense:.0f}MB over compressed)",
    )
    emit(
        f"fleet_propose_speedup_{label}", p50d / max(p50c, 1e-9),
        f"x dense p50 / compressed p50 (target >= 5x at k=100000)",
    )

    # -- interleaved min-time A/B: same noisy-neighbor conditions ----------
    if ab_rounds:
        comp2, fr2, st2 = _make_loop(k, grid, active=active, async_p=True)
        _drive(comp2, fr2, st2, 1)  # compile both sides before interleaving

        def one_cycle(loop, fracs, step):
            for _ in range(_RING):
                loop.push(fracs, step())
            return loop.tick().drift

        a_us, b_us = time_pair_min(
            lambda: one_cycle(dense, fracs, step),
            lambda: one_cycle(comp2, fr2, st2),
            rounds=ab_rounds,
        )
        emit(
            f"fleet_ab_min_dense_{label}", a_us,
            f"vs compressed {b_us:.0f}us -> {a_us / max(b_us, 1e-9):.1f}x "
            f"(interleaved min-time, {ab_rounds} rounds)",
        )
        del comp2
    del dense

    # -- the analytic footprint the grid program materializes per sweep ----
    # Emitted at the bench grid AND at the paper-fidelity G=512: the report
    # is closed-form, so the production sizing does not need the reduced-G
    # guard the *timing* rows run under.
    grids = (grid,) if grid == 512 else (grid, 512)
    for g in grids:
        rep = compress.compression_report(k, g, active)
        emit(
            f"fleet_posterior_bytes_dense_k{k}_g{g}", rep.dense_bytes / 1e6,
            f"MB per-sweep grid working set (K,2,G) f32 + chain scalars",
        )
        emit(
            f"fleet_posterior_bytes_compressed_k{k}_g{g}",
            rep.compressed_bytes / 1e6,
            f"MB active slab M={active} + Beta surrogate scalars -> "
            f"{rep.ratio:.1f}x smaller (target >= 10x at k=100000, G=512)",
        )


def _quantize_row(k: int) -> None:
    rng = np.random.default_rng(0)
    fr = rng.dirichlet(np.full(k, 2.0))
    t0 = time.perf_counter()
    counts = sched.quantize_fractions(fr, 8 * k)
    dt = (time.perf_counter() - t0) * 1e6
    assert counts.sum() == 8 * k
    emit(
        f"quantize_waterfill_k{k}", dt,
        "host rounding, O(K log K) water-fill shed/top-up",
    )


def main() -> None:
    """Full suite: paper-fidelity grids where feasible, all three decades."""
    _fleet_case(1_000, 512, 128, dense_ticks=5, comp_ticks=5, ab_rounds=3)
    _fleet_case(10_000, 128, 512, dense_ticks=3, comp_ticks=5, ab_rounds=2)
    _fleet_case(100_000, 64, 2048, dense_ticks=2, comp_ticks=3)
    _quantize_row(100_000)


def smoke_main() -> None:
    """CI subset: reduced-G guard keeps the CPU runner in minutes."""
    _fleet_case(1_000, 64, 128, dense_ticks=5, comp_ticks=5, ab_rounds=3)
    _fleet_case(10_000, 32, 512, dense_ticks=3, comp_ticks=4, ab_rounds=2)
    _fleet_case(100_000, 32, 2048, dense_ticks=2, comp_ticks=3)
    _quantize_row(100_000)


if __name__ == "__main__":
    main()
