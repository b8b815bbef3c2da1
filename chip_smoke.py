#!/usr/bin/env python3
"""Bring-up smoke run of the estimation service on a TPU.

    python chip_smoke.py              # one chip: service, kernel check, trainer
    python chip_smoke.py --chips 4    # the fleet axis sharded over four chips

One chip runs three phases in one process:

  * service: ``repro.serve.ServiceLoop`` at K = 10^4 workers with the default
    ``ServeConfig()``, fed telemetry by a heterogeneous ``SimulatedCluster``
    in a closed loop (each step's work follows the last published split);
  * reference: the fused posterior-grid kernel at the served shape against
    the jnp oracle ``repro.core.moments.log_posterior_grid``, both on the chip;
  * trainer: ``repro.train.Trainer`` with smollm-135m at full width and the
    partitioner on, as ``repro.launch.train`` builds it.

``--chips 4`` runs only the sharded service at K = 10^5
(``SchedulerConfig(mesh=ShardingConfig.auto())``) against the same seed and
telemetry on one chip with ``mesh=None``.

It stops before any phase unless JAX's first device is a TPU, and exits
non-zero on the first failed check.  The times it prints are smoke timings of
one cold run, not metrics.  The last line of standard output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402

KERNEL_OP = "tpu_custom_call"  # how a Mosaic kernel appears in compiled HLO
# Kernel vs oracle, max|d| / (1 + max|ref|): the bound tests/test_kernels.py
# holds the interpret-mode kernel to.
KERNEL_BOUND = 1e-4
# Sharded vs single-device posteriors: the bound of tests/test_sharding.py.
SHARD_BOUND = 1e-4
PUSHES_PER_TICK = 32  # telemetry rows buffered between drains (capacity 64)
OUT_DIR = os.path.join(ROOT, ".chip_smoke")  # the run's own output directory


def check(ok: bool, what: str) -> None:
    print(("ok    " if ok else "FAIL  ") + what, flush=True)
    if not ok:
        raise SystemExit(1)


def smoke_time(label: str, seconds: float) -> None:
    print(f"smoke timing (one cold run, not a metric): {label} {seconds:.2f} s")


def timed(label: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    smoke_time(label, time.perf_counter() - t0)
    return out


def device_check(chips: int):
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found {first.platform} "
            f"({first.device_kind}) x{len(devices)}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    print(f"device_kind={first.device_kind} platform={first.platform} "
          f"count={len(devices)}")
    return first, len(devices)


def make_cluster(k: int, seed: int):
    """K workers whose mean times span 1.3 decades, sigma = 0.1 mu."""
    from repro.distributed.simulated_cluster import SimulatedCluster, WorkerSpec

    rng = np.random.default_rng(seed)
    mu = np.exp(rng.uniform(np.log(1.0), np.log(20.0), k))
    specs = [WorkerSpec(mu=float(m), sigma=0.1 * float(m)) for m in mu]
    return SimulatedCluster(specs, seed=seed)


def compiled_tick_text(loop) -> str:
    from repro.serve import service

    lowered = service.tick.lower(loop.state, loop.config)
    return timed("tick compile", lowered.compile).as_text()


def drive(loops, cluster, ticks: int):
    """Closed loop: the first loop's published split decides each step's
    work, and every loop is pushed the same telemetry rows.  Returns the
    batch the last tick drains (taken before that tick) and its info."""
    import jax

    from repro.serve.ring import drain

    peek = jax.jit(drain)
    lead = loops[0]
    for tick in range(ticks):
        for _ in range(PUSHES_PER_TICK):
            fr = lead.fractions()
            times = cluster.step_times(fr)
            for loop in loops:
                loop.push(fr, times)
        if tick == ticks - 1:
            batch, _ = peek(lead.state.ring)
        t0 = time.perf_counter()
        infos = [loop.tick() for loop in loops]
        jax.block_until_ready([loop.state for loop in loops])
        print(f"tick {tick}: drained={int(infos[0].drained)} "
              f"proposed={bool(infos[0].proposed)} "
              f"drift={float(infos[0].drift):.4g}")
        smoke_time(f"tick {tick}", time.perf_counter() - t0)
    return batch


def service_phase(k: int, seed: int, ticks: int = 6):
    from repro import serve

    cluster = make_cluster(k, seed)
    config = serve.ServeConfig()
    loop = serve.ServiceLoop(k, config=config, seed=seed)
    print(f"service: K={k} capacity={config.capacity} "
          f"n_iters={config.sched.n_iters} grid={config.sched.grid_size} "
          f"opt_steps={config.sched.opt_steps} "
          f"num_points={config.sched.num_points}")
    check(KERNEL_OP in compiled_tick_text(loop),
          f"compiled tick contains {KERNEL_OP}")

    batch = drive([loop], cluster, ticks)
    c = loop.counters()
    print(f"counters: {c}")
    check(c["pushes"] == ticks * PUSHES_PER_TICK >= 2 * config.capacity,
          f"{c['pushes']} telemetry rows pushed")
    check(c["proposes"] >= 1, f"proposes={c['proposes']} >= 1")
    check(c["dropped"] == 0, f"dropped={c['dropped']} == 0")

    fr = loop.fractions()
    total = float(np.sum(fr, dtype=np.float64))
    check(bool(np.all(np.isfinite(fr))) and abs(total - 1.0) <= 1e-5,
          f"fractions finite, sum={total:.8f}")
    # Reported, not checked: the learned split does not beat the uniform one
    # here.  The default min_fraction (5e-3) is 50x the uniform share at
    # K = 10^4, so every worker whose share is below it is lifted to the
    # same value; and 20 sweeps per drain leave each alpha near its prior
    # (ROADMAP Queue 1 item 8).
    learned = cluster.oracle_makespan(fr)
    uniform = cluster.oracle_makespan(np.full(k, 1.0 / k))
    print(f"oracle makespan learned={learned:.6g} uniform={uniform:.6g} "
          f"ratio={learned / uniform:.4f} (reported, not checked)")
    return loop, batch


def reference_phase(loop, batch) -> None:
    """The served kernel launch against the jnp oracle, both on the chip."""
    import jax

    from repro.core.moments import exponent_grid, log_posterior_grid
    from repro.kernels import ops

    g = loop.state.sched.gibbs
    grid = exponent_grid(loop.config.sched.grid_size)
    args = (grid, batch.times, batch.fracs, g.mu, g.lam, g.alpha, g.beta,
            g.alpha_prior, g.beta_prior, batch.mask)
    print(f"reference: K={batch.times.shape[0]} G={grid.shape[0]} "
          f"N={batch.times.shape[1]}")
    kernel = jax.jit(ops.posterior_grid_fleet)
    check(KERNEL_OP in kernel.lower(*args).compile().as_text(),
          f"kernel launch contains {KERNEL_OP}")
    got = np.asarray(kernel(*args), np.float64)
    # The oracle's inner products are dots, which the TPU runs at bfloat16
    # precision unless asked for more; the kernel reduces in float32 on the
    # vector unit, so the oracle is held to float32 too.
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(log_posterior_grid)(*args), np.float64)
    check(bool(np.all(np.isfinite(got))), "kernel output finite")
    err = float(np.max(np.abs(got - want)) / (1.0 + np.max(np.abs(want))))
    check(err <= KERNEL_BOUND,
          f"kernel vs oracle max|d|/(1+max|ref|)={err:.3e} <= {KERNEL_BOUND}")


def trainer_phase(out_dir: str, seed: int, steps: int = 3) -> None:
    from repro.configs import RunConfig, get_arch
    from repro.configs.base import ShapeConfig
    from repro.distributed.simulated_cluster import SimulatedCluster, WorkerSpec
    from repro.train.trainer import Trainer

    cfg = get_arch("smollm-135m")
    print(f"trainer: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} seq_len=128 global_batch=16 workers=4")
    shape = ShapeConfig("chip-smoke", seq_len=128, global_batch=16, kind="train")
    run = RunConfig(
        model=cfg, shape=shape, seed=seed,
        checkpoint_dir=os.path.join(out_dir, "ckpt"),
        total_steps=steps, warmup_steps=1,
        checkpoint_every=steps + 1,  # no checkpoint inside the smoke run
        partitioner_refit_every=1,  # the partitioner observes every step
    )
    rng = np.random.default_rng(seed)
    specs = [
        WorkerSpec(mu=float(m), sigma=float(s))
        for m, s in zip(rng.uniform(5.0, 20.0, 4), rng.uniform(0.5, 2.0, 4))
    ]
    trainer = Trainer(run, cluster=SimulatedCluster(specs, seed=seed),
                      num_microbatches=8)
    report = trainer.train(steps)
    print(f"losses={report.losses} split={trainer.current_fracs()}")
    check(len(report.losses) == steps
          and bool(np.all(np.isfinite(report.losses))),
          f"{steps} finite full-width losses")
    check(int(trainer.partitioner.state.step) == steps,
          f"partitioner observed {steps} drains")


def device_bytes(devices) -> dict:
    import jax

    held = {d: 0 for d in devices}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            held[s.device] = held.get(s.device, 0) + s.data.nbytes
    return held


def sharded_phase(k: int, seed: int, chips: int, ticks: int = 3) -> None:
    import jax

    from repro import sched, serve
    from repro.core.sharding import ShardingConfig

    mesh = ShardingConfig.auto(chips)
    sharded = serve.ServiceLoop(
        k, config=serve.ServeConfig(sched=sched.SchedulerConfig(mesh=mesh)),
        seed=seed,
    )
    single = serve.ServiceLoop(k, config=serve.ServeConfig(), seed=seed)
    print(f"sharded: K={k} over {mesh.num_shards} chips vs mesh=None on "
          f"{jax.devices()[0]}")
    check(KERNEL_OP in compiled_tick_text(sharded),
          f"sharded tick contains {KERNEL_OP}")
    cluster = make_cluster(k, seed)
    drive([sharded, single], cluster, ticks)

    for d, n in device_bytes(jax.devices()).items():
        print(f"device {d.id}: {n / 2**20:.1f} MiB of live arrays")
    on_one = [
        jax.tree_util.keystr(p)
        for p, x in jax.tree_util.tree_leaves_with_path(sharded.state)
        if x.size >= k and len(x.sharding.device_set) == 1
    ]
    print(f"fleet-sized state leaves held by one device: {on_one}")
    check(sharded.state.sched.gibbs.mu.sharding.spec == mesh.spec(),
          "fleet posteriors sharded over the workers axis")

    a = sched.unit_params(sharded.state.sched)
    b = sched.unit_params(single.state.sched)
    f64 = lambda x: np.asarray(x, np.float64)
    worst = max(
        float(np.max(np.abs(f64(x) - f64(y)) / (SHARD_BOUND * (1 + np.abs(f64(y))))))
        for x, y in zip(a, b)
    )
    check(worst <= 1.0,
          f"posteriors agree: max|d|/(atol+rtol|ref|)={worst:.3g} <= 1 "
          f"(atol=rtol={SHARD_BOUND})")
    # Both runs solve the same belief (checked above) and differ only in the
    # order of the solve's reductions.  The splits are held to 2 K u of the
    # largest fraction: two roundings of a K-term float32 normalization, each
    # at most (K - 1) u relative for the sum and u for the division.  At
    # K = 10^5 the default min_fraction (5e-3) is 500 uniform shares, so a
    # solve whose shares all fall below it publishes the uniform split; when
    # both runs do, this check compares no learned split.
    fa, fb = sharded.fractions(), single.fractions()
    gap = float(np.max(np.abs(fa - fb)))
    bound = 2 * k * float(np.finfo(np.float32).eps / 2) * float(np.max(fb))
    off = max(float(np.max(np.abs(f - 1.0 / k))) * k for f in (fa, fb))
    check(gap <= bound,
          f"fractions agree: max|d| = {gap * k:.3g} <= {bound * k:.3g} "
          f"uniform shares (farthest from uniform: {off:.3g} shares)")
    c_sh, c_one = sharded.counters(), single.counters()
    check(c_sh == c_one and c_sh["dropped"] == 0, f"counters agree: {c_sh}")


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    first, count = device_check(args.chips)
    t_all = time.perf_counter()
    if args.chips == 4:
        timed("sharded phase", sharded_phase, 100_000, args.seed, args.chips)
    else:
        loop, batch = timed("service phase", service_phase, 10_000, args.seed)
        timed("reference phase", reference_phase, loop, batch)
        del loop, batch
        timed("trainer phase", trainer_phase, OUT_DIR, args.seed)
    smoke_time("all phases", time.perf_counter() - t_all)
    print(json.dumps({
        "ok": True,
        "device": {"platform": first.platform, "kind": first.device_kind,
                   "count": count},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
