"""Serving driver: ``python -m repro.launch.serve --arch tinyllama-1.1b``

Two modes:

  * **single-shot latency demo** (default): prefill + N decode steps on a
    (reduced by default) model, reporting per-phase latency — the classic
    driver, unchanged.
  * **partitioned serving** (``--rounds N`` or ``--serve-smoke``): request
    batches are split across heterogeneous replicas by the always-on
    estimation service (``repro.serve.ServiceLoop``).  The driver never
    calls the scheduler inline — it reads the last-good fractions from the
    service's double-buffered slot (a host read that cannot block on a
    Gibbs sweep), serves, and pushes the measured telemetry back into the
    service's device-resident ring.  Observe runs on every drained batch;
    the split re-solves only when the posterior moves (``docs/serving.md``).

On real hardware the same driver runs the full config under the production
mesh with the TP-only serving shardings from the dry-run; on this CPU
container it demonstrates the whole path (cache build, greedy decode,
QoS batch split across replicas, drift-gated re-partitioning).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.launch.cache import enable_compile_cache
from repro.models import model_zoo
from repro.models.layers import ApplyCtx
from repro.train import serve_step


def _latency_demo(cfg, args) -> None:
    """The original single-shot prefill/decode latency report."""
    params = model_zoo.init_model_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
        )
    }
    if cfg.vision_patches:
        batch["vision"] = jnp.zeros((args.batch, cfg.vision_patches, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros((args.batch, cfg.encoder_seq, cfg.d_model))

    max_len = args.prompt_len + args.gen_len + 8
    cache = model_zoo.init_cache(cfg, args.batch, max_len, jnp.float32)

    prefill = jax.jit(serve_step.make_prefill_step(cfg, ctx=ApplyCtx(mode="prefill")))
    decode = jax.jit(serve_step.make_decode_step(cfg, ctx=ApplyCtx(mode="decode")))

    t0 = time.perf_counter()
    token, cache = prefill(params, batch, cache)
    jax.block_until_ready(token)
    t_prefill = time.perf_counter() - t0

    outs = [token]
    t0 = time.perf_counter()
    for _ in range(args.gen_len - 1):
        token, cache = decode(params, token, cache)
        outs.append(token)
    jax.block_until_ready(token)
    t_decode = time.perf_counter() - t0

    gen = jnp.concatenate(outs, axis=1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   "
          f"decode: {t_decode/max(args.gen_len-1,1)*1e3:.1f} ms/token")
    print("generated token ids (seq 0):", np.asarray(gen[0]))


def _partitioned_serving(cfg, args) -> None:
    """Replica-partitioned serving fed by the always-on estimator service."""
    from repro import sched, serve
    from repro.distributed.simulated_cluster import SimulatedCluster, WorkerSpec

    params = model_zoo.init_model_params(jax.random.PRNGKey(0), cfg)
    # Jitted model closures are hoisted out of the request loop — requests
    # hit the jit cache, never a re-trace (shape changes of the local shard
    # compile once per distinct count).
    prefill = jax.jit(serve_step.make_prefill_step(cfg, ctx=ApplyCtx(mode="prefill")))
    decode = jax.jit(serve_step.make_decode_step(cfg, ctx=ApplyCtx(mode="decode")))

    # Heterogeneous replica speeds the estimator must discover online.
    rng = np.random.default_rng(0)
    specs = [
        WorkerSpec(mu=float(m), sigma=0.1 * float(m))
        for m in np.linspace(2.0, 6.0, args.replicas)
    ]
    cluster = SimulatedCluster(specs, seed=0)

    config = serve.ServeConfig(
        sched=sched.SchedulerConfig(
            n_iters=4, grid_size=64, num_points=128, opt_steps=40,
            mu_guess=float(np.mean([s.mu for s in specs])),
        ),
        capacity=2 * args.drain_every,
        drift_threshold=args.drift_threshold,
        max_staleness=8,
    )
    loop = serve.ServiceLoop(args.replicas, config=config, seed=1)

    max_len = args.prompt_len + args.gen_len + 8
    print("round | requests/replica | batch latency | service")
    for rnd in range(args.rounds):
        # Non-blocking read of the last-good split; never waits on a sweep.
        fr = loop.fractions()
        counts = sched.quantize_fractions(
            fr, args.batch, sched.unit_params(loop.state.sched),
            objective=config.sched.objective,
        )
        fr_actual = counts / counts.sum()

        # Really serve replica 0's shard on the local model (semantics demo;
        # each real replica would run its own shard the same way).
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (int(counts[0]), args.prompt_len)),
            jnp.int32,
        )
        cache = model_zoo.init_cache(cfg, int(counts[0]), max_len, jnp.float32)
        token, cache = prefill(params, {"tokens": toks}, cache)
        for _ in range(args.gen_len - 1):
            token, cache = decode(params, token, cache)
        jax.block_until_ready(token)

        # Telemetry: measured (simulated) per-replica latency for its share.
        times = cluster.step_times(fr_actual)
        loop.push(fr_actual, times, valid=np.isfinite(times))
        note = ""
        if (rnd + 1) % args.drain_every == 0:
            info = loop.tick()
            note = (f"drained={int(info.drained)} drift={float(info.drift):.3f} "
                    f"proposed={bool(info.proposed)}")
        lat = float(np.max(times[np.isfinite(times)]))
        print(f"  {rnd:3d} | {counts} | {lat:6.2f}s | {note}")

    c = loop.counters()
    fr = loop.fractions()
    eq = cluster.oracle_makespan(np.full(args.replicas, 1.0 / args.replicas))
    lr = cluster.oracle_makespan(fr)
    print(f"learned split {np.round(fr, 3)}  "
          f"oracle makespan equal={eq:.2f}s learned={lr:.2f}s")
    print(f"service: {c['pushes']} pushes, {c['drains']} drains, "
          f"{c['proposes']} proposes "
          f"(skip rate {1.0 - c['proposes'] / max(c['drains'], 1):.2f}), "
          f"{c['dropped']} dropped")
    if args.serve_smoke:
        ok = c["proposes"] >= 1 and c["drains"] > c["proposes"]
        print(f"serve-smoke {'OK' if ok else 'FAILED'}")
        if not ok:
            raise SystemExit(1)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=0,
                    help="partitioned-serving rounds via repro.serve "
                         "(0 = single-shot latency demo)")
    ap.add_argument("--drain-every", type=int, default=4,
                    help="service drain cadence in rounds")
    ap.add_argument("--drift-threshold", type=float, default=0.05,
                    help="posterior drift gate for re-solving the split")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="tiny fixed partitioned-serving run for CI: reduced "
                         "arch, few rounds, exit 1 unless the service "
                         "proposed at least once and skipped at least once")
    args = ap.parse_args()

    if args.serve_smoke:
        args.arch = "smollm-135m"
        args.reduced = True
        args.batch = 8
        args.prompt_len = 8
        args.gen_len = 4
        args.rounds = 12
        args.drain_every = 2
        args.replicas = 3
        # Steady-state skips must show up within few drains: gate a little
        # above the converged-posterior jitter of this fixed-seed workload.
        args.drift_threshold = 0.12

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    if args.rounds > 0:
        _partitioned_serving(cfg, args)
    else:
        _latency_demo(cfg, args)


if __name__ == "__main__":
    main()
