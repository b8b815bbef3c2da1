"""Pure-functional online scheduler API (state-in/state-out).

The paper's Bayesian partitioner re-cast as explicit pytree state plus pure
transitions — every entry point is jit-compatible, vmappable across tenant
fleets, and checkpointable through ``repro.checkpoint.CheckpointManager``.
The full cycle — learn from telemetry, propose a split, score anomalies:

>>> import jax, jax.numpy as jnp
>>> from repro import sched
>>> config = sched.SchedulerConfig(n_iters=2, grid_size=32, num_points=64,
...                                opt_steps=10)
>>> state = sched.init(config, num_workers=3, key=jax.random.PRNGKey(0))
>>> f = jax.random.uniform(jax.random.PRNGKey(1), (3, 16), minval=0.1,
...                        maxval=0.9)
>>> t = f**0.9 * jnp.asarray([[5.0], [10.0], [20.0]])   # hidden unit speeds
>>> telemetry = sched.Telemetry(fracs=f, times=t)
>>> state, ll = sched.observe(state, telemetry, config)
>>> fracs, stats = sched.propose(state, config)
>>> fracs.shape, bool(abs(float(jnp.sum(fracs)) - 1.0) < 1e-5)
((3,), True)
>>> state, scores = sched.anomaly(state, telemetry, config)
>>> scores.shape
(3,)

``Scheduler`` is the thin imperative shell (config + current state) used by
the trainer/server loops.  For two units, ``optimal_two_way_fraction`` picks
the best split on the paper's (mean, variance) frontier for an ``Objective``.

Multi-stage pipelines lift the same API to workflow DAGs (``repro.sched.dag``):

    state = sched.init_dag(config, dag, key)          # dag: WorkflowDAG
    state, ll     = sched.observe_dag(state, telemetry, config)  # (S, K, N)
    fracs, stats  = sched.propose_dag(state, dag, config)        # (S, K)

Estimation of the whole DAG is ONE stacked (S, K, N) program — the stage
axis folds into the fleet axis, never a Python loop over stages.

Fleet-axis scale-out (multi-device / multi-host; see ``docs/scaling.md``):
``SchedulerConfig.mesh`` takes a ``ShardingConfig`` and the SAME transitions
partition the worker axis across a device mesh with ``shard_map`` — results
match the single-device program bitwise, so it composes with everything
above (checkpointing, vmap-over-tenants, DAGs):

>>> mesh = sched.ShardingConfig.auto()       # 1-D mesh over local devices
>>> sconfig = sched.SchedulerConfig(n_iters=2, grid_size=32, mesh=mesh)
>>> sstate = sched.init(sconfig, num_workers=3, key=jax.random.PRNGKey(0))
>>> sstate, sll = sched.observe(sstate, telemetry, sconfig)
>>> bool(jnp.all(sstate.gibbs.key == state.gibbs.key))  # PRNG: bitwise
True
>>> bool(jnp.max(jnp.abs(sll - ll))                     # posteriors: fp-close
...      <= 1e-3 * (1.0 + jnp.max(jnp.abs(ll))))
True
"""
from .dag import (
    DagProposeStats,
    DagState,
    WorkflowDAG,
    dag_stats,
    effective_stage_moments,
    init_dag,
    observe_dag,
    path_lengths,
    propose_dag,
    stage_params,
    uniform_fractions,
)
from repro.core.sharding import ShardingConfig

from .objectives import Objective, as_stage_objectives, optimal_two_way_fraction
from .quantize import quantize_dag_fractions, quantize_fractions
from .scheduler import (
    ProposeStats,
    Scheduler,
    SchedulerConfig,
    SchedulerState,
    Telemetry,
    add_workers,
    admit_workers,
    advance_fleet,
    anomaly,
    capacity,
    flag_stragglers,
    grow_capacity,
    init,
    num_workers,
    observe,
    propose,
    remove_workers,
    retire_workers,
    solve_fractions,
    unit_params,
    unit_params_from_gibbs,
)

__all__ = [
    "DagProposeStats",
    "DagState",
    "Objective",
    "ProposeStats",
    "Scheduler",
    "SchedulerConfig",
    "SchedulerState",
    "ShardingConfig",
    "Telemetry",
    "WorkflowDAG",
    "add_workers",
    "admit_workers",
    "advance_fleet",
    "anomaly",
    "as_stage_objectives",
    "capacity",
    "dag_stats",
    "effective_stage_moments",
    "flag_stragglers",
    "grow_capacity",
    "init",
    "init_dag",
    "num_workers",
    "observe",
    "observe_dag",
    "optimal_two_way_fraction",
    "path_lengths",
    "propose",
    "propose_dag",
    "quantize_dag_fractions",
    "quantize_fractions",
    "remove_workers",
    "retire_workers",
    "solve_fractions",
    "stage_params",
    "uniform_fractions",
    "unit_params",
    "unit_params_from_gibbs",
]
