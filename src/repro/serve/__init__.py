"""Streaming serving loop: the estimator as an always-on service.

Push-mode path for fleets that feed telemetry continuously and cannot block
on a Gibbs sweep: a device-resident ``TelemetryRing`` buffers observations,
``tick`` drains whole batches through the fleet-native estimator, and the
simplex solve re-runs only when the posterior actually moved (drift-gated
cadence with a hard staleness cap).  The gate self-calibrates by default —
an online EWMA baseline of the drift statistic (``repro.serve.gate``)
replaces the fleet-size-dependent fixed threshold; pass an explicit
``drift_threshold`` for the legacy fixed gate.  See ``docs/serving.md``
and ``docs/hierarchy.md``.

>>> import jax, jax.numpy as jnp
>>> from repro import serve, sched
>>> config = serve.ServeConfig(
...     sched=sched.SchedulerConfig(n_iters=2, grid_size=32, num_points=64,
...                                 opt_steps=10),
...     capacity=8, drift_threshold=0.05, max_staleness=4)
>>> loop = serve.ServiceLoop(3, config=config, seed=0)
>>> import numpy as np
>>> bool(np.allclose(loop.fractions(), 1 / 3))  # placeholder until learned
True
>>> rng = jax.random.PRNGKey(1)
>>> for i in range(8):                          # 8 telemetry rows buffered
...     f = jax.random.uniform(jax.random.fold_in(rng, i), (3,), minval=0.1,
...                            maxval=0.9)
...     loop.push(f, f**0.9 * jnp.asarray([5.0, 10.0, 20.0]))
>>> info = loop.tick()                          # drain -> observe -> propose
>>> (int(info.drained), bool(info.proposed))
(8, True)
>>> bool(abs(float(loop.fractions().sum()) - 1.0) < 1e-5)
True
"""
from .gate import GateState, gate_init, gate_threshold, gate_update
from .ring import DrainedBatch, TelemetryRing, drain, push, push_rows, ring_init
from .service import (
    ServeConfig,
    ServeState,
    ServiceLoop,
    TickInfo,
    init,
    posterior_drift,
    solve_published,
    tick,
    tick_with_params,
)

__all__ = [
    "DrainedBatch",
    "GateState",
    "ServeConfig",
    "ServeState",
    "ServiceLoop",
    "TelemetryRing",
    "TickInfo",
    "drain",
    "gate_init",
    "gate_threshold",
    "gate_update",
    "init",
    "posterior_drift",
    "push",
    "push_rows",
    "ring_init",
    "solve_published",
    "tick",
    "tick_with_params",
]
