"""Stage-structured workflow DAGs: stacked estimation + composed frontier.

The paper partitions ONE workflow stage across K uncertain units; real
workflows are pipelines.  This module lifts the whole scheduler stack from a
simplex to a *graph*:

  * ``WorkflowDAG`` — S stages (each a K-worker fleet with its own exponent
    posteriors) plus a static precedence topology.  Serial chains are the
    common case; general DAGs compose via topological reduction
    (``frontier.dag_completion_moments``).  Stochastic annotations make the
    topology itself uncertain: per-stage execution probabilities
    (``exec_probs`` — conditional branches), geometric rework loops
    (``rework_probs`` + ``max_retries``), and heterogeneous per-stage fleet
    widths (``stage_workers`` — pad to max K, dead columns masked to exactly
    zero fraction).
  * ``DagState`` — one ``GibbsState`` whose leaves carry (S, K) leading axes.
    Estimation NEVER loops over stages: ``observe_dag`` / ``core.gibbs.fit_dag``
    fold the stage axis into the fleet axis and advance the entire (S, K, N)
    telemetry block through one fleet-native ``gibbs_batch`` — a single fused
    Pallas launch per sweep sees S*K workers.  Stochastic annotations change
    NOTHING here: the estimator learns per-attempt worker behaviour, and all
    branch/rework structure lives in the composition layer.
  * ``propose_dag`` — partitions stage by stage against the shared
    ``Objective`` (or a per-stage ``objectives`` tuple).  The moment-separable
    kinds decompose exactly for chains (E and Var of a sum both add);
    budgeted kinds (``var_budget``, ``deadline``) allocate the end-to-end
    budget across stages, and the critical-path-aware variant spends the risk
    budget where variance hurts end-to-end latency most.  On a *stochastic*
    DAG the allocation runs over EFFECTIVE stage moments (what each stage
    contributes after rework amplification and branch thinning —
    ``effective_stage_moments``), and a joint end-to-end refinement pass
    descends on all S*K logits at once against the composed objective,
    keeping whichever of {per-stage, joint} actually scores better: the
    per-stage decomposition cannot see that variance bought at a noisy
    fork/join costs E[max] downstream, the joint pass can.

All propose-side transitions are pure and jit-compatible: the topology is a
frozen, hashable dataclass (jit-static), stage moments stay traced.
Degenerate annotations (p = 1 branches, zero rework, full-width stages) are
detected statically (``is_stochastic``) and take the deterministic code path
bitwise — ``tests/test_stochastic.py`` pins this leaf-for-leaf.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import gibbs
from repro.core.frontier import (
    UnitParams,
    dag_completion_moments,
    mean_var_completion,
    stochastic_stage_moments,
    truncated_geometric_moments,
)
from repro.core.sharding import constrain_fleet

from .objectives import Objective, as_stage_objectives, score_moments_dynamic
from .scheduler import (
    SchedulerConfig,
    Telemetry,
    advance_fleet,
    solve_fractions,
    unit_params_from_gibbs,
)

Array = jax.Array


# --------------------------------------------------------------------------
# topology
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class WorkflowDAG:
    """Static topology of a stage-structured workflow.

    ``preds[i]`` lists the stages that must finish before stage i starts;
    stages must be numbered topologically (every predecessor index < i), so
    the structure is acyclic by construction and composition can run one
    forward pass.  ``num_workers`` is the per-stage fleet width K — the
    (S, K, N) telemetry block stacks into one fused estimation program;
    ``stage_workers`` optionally narrows individual stages (K_s <= K):
    columns beyond a stage's width are dead — masked out of estimation and
    pinned to exactly 0.0 fraction by the proposal.

    Stochastic annotations (all optional, all per-stage tuples so the
    dataclass stays hashable and jit-static):

      exec_probs[i]    probability stage i executes at all (conditional
                       branch on upstream data); a skipped stage contributes
                       zero time but still forwards its predecessors' finish.
      rework_probs[i]  probability an attempt of stage i must be REDONE
                       (per-attempt failure), so attempt counts are
                       Geometric(1 - rework_probs[i]) ...
      max_retries[i]   ... truncated at this cap (defaults to 8 whenever
                       ``rework_probs`` is given).

    Hashable and immutable: rides through ``jax.jit`` as a static argument.
    """

    preds: Tuple[Tuple[int, ...], ...]
    num_workers: int
    names: Optional[Tuple[str, ...]] = None
    exec_probs: Optional[Tuple[float, ...]] = None
    rework_probs: Optional[Tuple[float, ...]] = None
    max_retries: Optional[Tuple[int, ...]] = None
    stage_workers: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        for i, ps in enumerate(self.preds):
            for p in ps:
                if not 0 <= p < i:
                    raise ValueError(
                        f"stage {i} depends on stage {p}: stages must be "
                        "numbered topologically (predecessor < successor); "
                        "cycles are unrepresentable"
                    )
        s = len(self.preds)
        if self.names is not None and len(self.names) != s:
            raise ValueError("names must match num_stages")
        # Normalize annotations to plain tuples (hashability under jit).
        for field in ("exec_probs", "rework_probs"):
            val = getattr(self, field)
            if val is None:
                continue
            val = tuple(float(x) for x in val)
            object.__setattr__(self, field, val)
            if len(val) != s:
                raise ValueError(f"{field} must have one entry per stage")
            if not all(0.0 <= x <= 1.0 for x in val):
                raise ValueError(f"{field} entries must lie in [0, 1]")
        if self.rework_probs is not None and any(
            x >= 1.0 for x in self.rework_probs
        ):
            raise ValueError(
                "rework_probs must be < 1 (an always-failing stage never "
                "completes)"
            )
        if self.max_retries is not None and self.rework_probs is None:
            raise ValueError("max_retries without rework_probs is meaningless")
        if self.rework_probs is not None:
            caps = self.max_retries
            caps = (8,) * s if caps is None else tuple(int(r) for r in caps)
            object.__setattr__(self, "max_retries", caps)
            if len(caps) != s:
                raise ValueError("max_retries must have one entry per stage")
            if not all(r >= 1 for r in caps):
                raise ValueError("max_retries entries must be >= 1")
        if self.stage_workers is not None:
            widths = tuple(int(k) for k in self.stage_workers)
            object.__setattr__(self, "stage_workers", widths)
            if len(widths) != s:
                raise ValueError("stage_workers must have one entry per stage")
            if not all(1 <= k <= self.num_workers for k in widths):
                raise ValueError(
                    "stage_workers entries must lie in [1, num_workers]"
                )

    # -- constructors ------------------------------------------------------
    @staticmethod
    def chain(num_stages: int, num_workers: int) -> "WorkflowDAG":
        """A serial pipeline: stage i feeds stage i+1."""
        preds = tuple(() if i == 0 else (i - 1,) for i in range(num_stages))
        return WorkflowDAG(preds=preds, num_workers=num_workers)

    @staticmethod
    def from_edges(
        num_stages: int, edges: Tuple[Tuple[int, int], ...], num_workers: int
    ) -> "WorkflowDAG":
        """Build from (upstream, downstream) pairs (topologically numbered)."""
        preds = [[] for _ in range(num_stages)]
        for u, v in edges:
            if not 0 <= v < num_stages:
                raise ValueError(f"edge ({u}, {v}) out of range")
            preds[v].append(u)
        return WorkflowDAG(
            preds=tuple(tuple(sorted(set(p))) for p in preds),
            num_workers=num_workers,
        )

    # -- annotated copies --------------------------------------------------
    def with_stochastic(
        self,
        *,
        exec_probs: Optional[Sequence[float]] = None,
        rework_probs: Optional[Sequence[float]] = None,
        max_retries: Optional[Sequence[int]] = None,
    ) -> "WorkflowDAG":
        """Copy with branch/rework annotations (validated, tuple-normalized)."""
        return dataclasses.replace(
            self,
            exec_probs=None if exec_probs is None else tuple(exec_probs),
            rework_probs=None if rework_probs is None else tuple(rework_probs),
            max_retries=None if max_retries is None else tuple(max_retries),
        )

    def with_stage_workers(self, widths: Sequence[int]) -> "WorkflowDAG":
        """Copy with heterogeneous per-stage fleet widths (K_s <= K)."""
        return dataclasses.replace(self, stage_workers=tuple(widths))

    # -- structure ---------------------------------------------------------
    @property
    def num_stages(self) -> int:
        return len(self.preds)

    @property
    def sinks(self) -> Tuple[int, ...]:
        has_succ = {p for pp in self.preds for p in pp}
        return tuple(i for i in range(self.num_stages) if i not in has_succ)

    @property
    def is_chain(self) -> bool:
        return all(
            ps == (() if i == 0 else (i - 1,)) for i, ps in enumerate(self.preds)
        )

    def succs(self, i: int) -> Tuple[int, ...]:
        return tuple(j for j in range(self.num_stages) if i in self.preds[j])

    @property
    def is_stochastic(self) -> bool:
        """True only for NON-degenerate randomness.

        p = 1.0 branches and zero-probability (or cap-1) rework change no
        number, so they are routed through the deterministic code path —
        that is what makes the bitwise-regression guarantee structural
        rather than numerical luck.
        """
        if self.exec_probs is not None and any(p < 1.0 for p in self.exec_probs):
            return True
        if self.rework_probs is not None:
            return any(
                r > 0.0 and cap > 1
                for r, cap in zip(self.rework_probs, self.max_retries)
            )
        return False

    def stage_live(self) -> Optional[Array]:
        """(S, K) {0, 1} per-stage worker mask, or None when homogeneous."""
        if self.stage_workers is None:
            return None
        col = jnp.arange(self.num_workers)[None, :]
        widths = jnp.asarray(self.stage_workers, jnp.int32)[:, None]
        return (col < widths).astype(jnp.float32)


def path_lengths(dag: WorkflowDAG, stage_means: Array) -> Tuple[Array, Array]:
    """Longest expected path THROUGH each stage, and the critical-path length.

    ``through[i] = fwd[i] + bwd[i] - mean[i]`` where fwd/bwd are the longest
    expected path ending at / starting from stage i.  The topology is static
    (Python loop over stage indices) while the means stay traced, so this
    jits.  ``through[i] / max(through)`` is the criticality weight used by
    the budget allocator: 1 on the critical path, < 1 for stages whose
    longest path has slack against it.  On a stochastic DAG pass EFFECTIVE
    means (``effective_stage_moments``) so criticality reflects what stages
    actually contribute.
    """
    s = dag.num_stages
    fwd: list = [None] * s
    for i in range(s):
        up = [fwd[p] for p in dag.preds[i]]
        start = functools.reduce(jnp.maximum, up) if up else jnp.asarray(0.0, jnp.float32)
        fwd[i] = start + stage_means[i]
    bwd: list = [None] * s
    for i in reversed(range(s)):
        down = [bwd[j] for j in dag.succs(i)]
        tail = functools.reduce(jnp.maximum, down) if down else jnp.asarray(0.0, jnp.float32)
        bwd[i] = tail + stage_means[i]
    through = jnp.stack([fwd[i] + bwd[i] - stage_means[i] for i in range(s)])
    return through, jnp.max(through)


# --------------------------------------------------------------------------
# stochastic composition helpers
# --------------------------------------------------------------------------
def _stochastic_factors(dag: WorkflowDAG) -> Tuple[Array, Array, Array]:
    """(p, E[N], Var[N]) per stage from the static annotations."""
    s = dag.num_stages
    p = jnp.asarray(
        dag.exec_probs if dag.exec_probs is not None else (1.0,) * s,
        jnp.float32,
    )
    if dag.rework_probs is not None:
        n_mean, n_var = truncated_geometric_moments(
            1.0 - jnp.asarray(dag.rework_probs, jnp.float32), dag.max_retries
        )
    else:
        n_mean = jnp.ones((s,), jnp.float32)
        n_var = jnp.zeros((s,), jnp.float32)
    return p, n_mean, n_var


def effective_stage_moments(
    dag: WorkflowDAG, stage_means: Array, stage_vars: Array
) -> Tuple[Array, Array]:
    """Per-attempt stage moments -> what each stage contributes end-to-end.

    Applies the geometric-rework compound-sum transform then the Bernoulli
    branch mixture (``frontier.stochastic_stage_moments``).  A DAG without
    non-degenerate annotations passes through UNTOUCHED — same arrays, same
    bits — which is what keeps the deterministic path regression-exact.
    """
    if not dag.is_stochastic:
        return stage_means, stage_vars
    return stochastic_stage_moments(
        stage_means,
        stage_vars,
        exec_probs=(
            None
            if dag.exec_probs is None
            else jnp.asarray(dag.exec_probs, jnp.float32)
        ),
        success_probs=(
            None
            if dag.rework_probs is None
            else 1.0 - jnp.asarray(dag.rework_probs, jnp.float32)
        ),
        max_retries=dag.max_retries,
    )


# --------------------------------------------------------------------------
# state + estimation (stacked — never a Python loop over stages)
# --------------------------------------------------------------------------
class DagState(NamedTuple):
    """Everything the DAG scheduler has learned; a registered pytree.

    ``gibbs`` leaves carry (S, K, ...) leading axes — stage-major, matching
    ``gibbs.fold_stage_axis`` — so checkpointing, vmap-over-tenants, and the
    fused estimation path all treat the DAG as one S*K fleet.
    """

    gibbs: gibbs.GibbsState  # per-stage-per-worker posteriors, leaves (S, K, ...)
    step: Array  # scalar, observe_dag() calls so far
    key: Array  # DAG-scheduler PRNG key


class DagProposeStats(NamedTuple):
    """Per-stage and end-to-end statistics of a proposed stage-wise split.

    On a stochastic DAG ``stage_e`` / ``stage_var`` are the EFFECTIVE
    contributions (rework-amplified, branch-thinned) and ``e_t`` / ``var``
    compose them; on a deterministic DAG they are the raw per-attempt
    makespan moments, unchanged from PR 4.
    """

    stage_e: Array  # (S,) expected makespan of each stage at its split
    stage_var: Array  # (S,) completion-time variance of each stage
    e_t: Array  # end-to-end expected completion (topological composition)
    var: Array  # end-to-end completion variance
    score: Array  # DAG-level objective score (lower is better)


@functools.partial(jax.jit, static_argnames=("config", "dag"))
def init_dag(config: SchedulerConfig, dag: WorkflowDAG, key: Array) -> DagState:
    """Fresh beliefs for every stage's fleet.

    The DAG's programs open no host span, so this also starts the
    ``repro.obs`` trace tally (``obs.hook``): ``observe_dag`` and
    ``propose_dag`` are then counted from their first trace.
    """
    obs.hook()
    s, k = dag.num_stages, dag.num_workers
    key, sub = jax.random.split(key)
    keys = jax.random.split(sub, s * k)
    fleet = jax.vmap(lambda kk: gibbs.init_state(kk, mu_guess=config.mu_guess))(keys)
    return DagState(
        # With config.mesh the per-stage fleets are sharded over the worker
        # axis (leaf axis 1) from birth; observe_dag's folded S*K program
        # re-lays them out stage-major per shard as needed.
        gibbs=constrain_fleet(
            gibbs.unfold_stage_axis(fleet, s), config.mesh, axis=1
        ),
        step=jnp.zeros((), jnp.int32),
        key=key,
    )


@functools.partial(jax.jit, static_argnames=("config", "dag"))
def observe_dag(
    state: DagState,
    telemetry: Telemetry,
    config: SchedulerConfig = SchedulerConfig(),
    mask: Optional[Array] = None,
    dag: Optional[WorkflowDAG] = None,
) -> Tuple[DagState, Array]:
    """Advance every stage's posteriors from one (S, K, N) telemetry block.

    The stage axis folds into the fleet axis, so the whole DAG advances as
    ONE stacked fleet-native ``gibbs_batch`` program — with the Pallas path
    each sweep's grid posterior is a single kernel launch covering S*K
    workers and both exponents.  With ``config.mesh`` that folded S*K axis
    is partitioned across the device mesh (``shard_map``), so a wide or
    deep DAG scales out without changing this call.

    ``mask`` optionally invalidates telemetry elements (broadcastable to the
    (S, K, N) times).  Passing a ``dag`` with heterogeneous ``stage_workers``
    additionally masks every dead column automatically — whatever garbage a
    padded row carries is an exact no-op on its parked posterior.  Returns
    per-stage-per-worker (S, K) log-likelihood.
    """
    s = telemetry.times.shape[0]
    if dag is not None and dag.stage_workers is not None:
        lv = dag.stage_live()[:, :, None]  # (S, K, 1)
        mask = (
            lv
            if mask is None
            else jnp.broadcast_to(mask, telemetry.times.shape) * lv
        )
    fold = gibbs.fold_stage_axis
    with jax.named_scope("gibbs_advance"):
        fleet, ll = advance_fleet(
            fold(state.gibbs),
            fold(telemetry.times),
            fold(telemetry.fracs),
            config,
            mask=None if mask is None else fold(jnp.broadcast_to(mask, telemetry.times.shape)),
        )
    return (
        state._replace(gibbs=gibbs.unfold_stage_axis(fleet, s), step=state.step + 1),
        ll.reshape(telemetry.times.shape[:2]),
    )


def stage_params(state: DagState, *, use_samples: bool = False) -> UnitParams:
    """Current point estimates as frontier parameters, leaves (S, K)."""
    return unit_params_from_gibbs(state.gibbs, use_samples=use_samples)


# --------------------------------------------------------------------------
# partitioning
# --------------------------------------------------------------------------
def uniform_fractions(dag: WorkflowDAG) -> Array:
    """The naive baseline: every stage split 1/K_s across its live workers."""
    live = dag.stage_live()
    if live is None:
        return jnp.full(
            (dag.num_stages, dag.num_workers), 1.0 / dag.num_workers, jnp.float32
        )
    return live / jnp.sum(live, axis=-1, keepdims=True)


def dag_stats(
    dag: WorkflowDAG,
    fracs: Array,
    params: UnitParams,
    objective: Objective = Objective(),
    *,
    num_points: int = 512,
) -> DagProposeStats:
    """Compose per-stage makespan moments into end-to-end DAG statistics.

    Stochastic annotations are folded in between the per-stage quadrature and
    the topological reduction: each stage's per-attempt moments become its
    effective contribution (``effective_stage_moments``) before composition.
    """
    stage_e, stage_var = jax.vmap(
        lambda fr, p: mean_var_completion(fr, p, num_points)
    )(fracs, params)
    stage_e, stage_var = effective_stage_moments(dag, stage_e, stage_var)
    e_t, var = dag_completion_moments(
        dag.preds, stage_e, stage_var, num_points=num_points
    )
    if objective.needs_cdf():
        # Normal-matched end-to-end tail: P(T <= d) under the composed moments.
        from repro.core.distributions import normal_cdf

        score = -normal_cdf(
            jnp.asarray(objective.deadline, jnp.float32),
            e_t,
            jnp.sqrt(jnp.maximum(var, 1e-18)),
        )
    else:
        score = objective.score_moments(e_t, var)
    return DagProposeStats(
        stage_e=stage_e, stage_var=stage_var, e_t=e_t, var=var, score=score
    )


def _dag_objective_score(
    dag: WorkflowDAG,
    fracs: Array,
    params: UnitParams,
    objective: Objective,
    num_points: int,
    *,
    smooth: bool = False,
) -> Array:
    """Composed end-to-end objective score of an (S, K) split (differentiable)."""
    stage_e, stage_var = jax.vmap(
        lambda fr, p: mean_var_completion(fr, p, num_points)
    )(fracs, params)
    stage_e, stage_var = effective_stage_moments(dag, stage_e, stage_var)
    e_t, var = dag_completion_moments(
        dag.preds, stage_e, stage_var, num_points=num_points
    )
    if objective.needs_cdf():
        from repro.core.distributions import normal_cdf

        p_meet = normal_cdf(
            jnp.asarray(objective.deadline, jnp.float32),
            e_t,
            jnp.sqrt(jnp.maximum(var, 1e-18)),
        )
        if smooth:
            return -jnp.log(jnp.maximum(p_meet, 1e-12))
        return -p_meet
    return score_moments_dynamic(
        objective.kind,
        e_t,
        var,
        objective.risk_aversion,
        objective.var_budget,
        smooth=smooth,
    )


def _joint_refine(
    dag: WorkflowDAG,
    fracs: Array,
    params: UnitParams,
    objective: Objective,
    config: SchedulerConfig,
    live: Optional[Array],
) -> Array:
    """End-to-end Adam refinement of ALL stage splits at once.

    The per-stage decomposition is blind to cross-stage coupling that only
    the composed objective sees — on a stochastic DAG, trading a little
    per-stage expected time for less variance at a noisy fork/join lowers the
    end-to-end E[max].  This pass descends on the full (S, K) logit tensor
    against the composed (effective-moment) objective.  The caller keeps the
    result only if it beats the per-stage solution under the non-smooth
    composed score, so refinement can never lose ground.
    """
    num_points = config.num_points

    def smooth_loss(logits: Array) -> Array:
        if live is not None:
            logits = jnp.where(live > 0, logits, -1e9)
        f = jax.nn.softmax(logits, axis=-1)
        return _dag_objective_score(
            dag, f, params, objective, num_points, smooth=True
        )

    grad = jax.grad(smooth_loss)
    logits0 = jnp.log(jnp.maximum(fracs, 1e-9))

    def adam_step(carry, _):
        logits, m, v, t = carry
        g = grad(logits)
        t = t + 1.0
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1.0 - 0.9**t)
        vh = v / (1.0 - 0.999**t)
        logits = logits - config.opt_lr * mh / (jnp.sqrt(vh) + 1e-8)
        return (logits, m, v, t), None

    zeros = jnp.zeros_like(logits0)
    (logits, _, _, _), _ = jax.lax.scan(
        adam_step, (logits0, zeros, zeros, jnp.asarray(0.0)), None,
        length=config.opt_steps,
    )
    if live is not None:
        logits = jnp.where(live > 0, logits, -1e9)
    f = jax.nn.softmax(logits, axis=-1)
    # Same per-worker floor discipline as solve_fractions, rows renormalized.
    if live is None:
        f = jnp.maximum(f, config.min_fraction)
    else:
        f = jnp.where(live > 0, jnp.maximum(f, config.min_fraction), 0.0)
    return f / jnp.sum(f, axis=-1, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("dag", "config", "critical_path_aware", "objectives"),
)
def propose_dag(
    state: DagState,
    dag: WorkflowDAG,
    config: SchedulerConfig = SchedulerConfig(),
    *,
    critical_path_aware: bool = True,
    objectives: Optional[Tuple[Objective, ...]] = None,
    params: Optional[UnitParams] = None,
) -> Tuple[Array, DagProposeStats]:
    """Objective-optimal stage-wise splits under the current beliefs.

    Returns fractions (S, K) — each row on the (live-masked) K-simplex —
    plus composed end-to-end statistics.  Decomposition by objective kind:

      mean       Stage-separable for chains: E[sum] = sum E -> each stage
                 independently minimizes its expected makespan.
      mean_var   Separable too (Var of a sum of independent stage times
                 adds); the critical-path-aware variant scales each stage's
                 risk aversion by its criticality — variance on a slack
                 branch cannot move end-to-end latency, so it is not worth
                 paying expected time to remove.
      var_budget The end-to-end variance budget is allocated across stages
                 proportional to their unconstrained variance share (times
                 criticality when critical-path-aware), then each stage
                 solves its own budgeted problem; one reallocation round
                 returns slack from stages that beat their slice to the
                 stages that clipped against theirs.
      deadline   The end-to-end deadline splits along paths: stage s gets
                 d_s = d * E_s / L_s with L_s the longest expected path
                 through s.  Along ANY path the allocated deadlines sum to
                 <= d, so the product of per-stage P(t_s <= d_s) lower-bounds
                 P(T <= d) — each stage then maximizes its own term.

    On a stochastic DAG (non-degenerate ``exec_probs`` / ``rework_probs``)
    every cross-stage quantity above — criticality, variance shares, budget
    and deadline slices — is computed from EFFECTIVE stage moments, and the
    end-to-end budgets are converted to the per-attempt level each stage
    solve actually controls (a stage retried E[N] times on a p-probability
    branch turns one unit of per-attempt variance into p*E[N] units of
    effective variance).  A joint refinement pass then descends on all S*K
    logits against the composed objective and is kept only if it wins
    (``_joint_refine``).  Degenerate annotations take the deterministic path
    bitwise.

    ``objectives`` (a per-stage tuple, jit-static) switches each stage to
    its OWN objective — budgets and deadlines are then per-stage constraints,
    not end-to-end allocations; stages sharing an objective value still solve
    in one vmapped program, and the returned stats score the composition
    under ``config.objective``.  ``params`` overrides the posterior point
    estimates (e.g. the TRUE worker parameters when evaluating against the
    MC oracle).

    All stage solves are vmapped ``solve_fractions`` programs (the objective
    kind is static; per-stage budget/deadline slices ride through as traced
    overrides), not a Python loop of per-stage compilations.
    """
    if params is None:
        params = stage_params(state)
    live = dag.stage_live()
    stochastic = dag.is_stochastic
    solve_kw = dict(
        steps=config.opt_steps,
        lr=config.opt_lr,
        num_points=config.num_points,
        min_fraction=config.min_fraction,
    )

    @jax.named_scope("stage_solve")
    def vsolve(p, objective, live_rows=None, **overrides):
        """One vmapped solve across a leading stage axis."""
        names = tuple(k for k, v in overrides.items() if v is not None)
        vals = tuple(overrides[k] for k in names)
        if live_rows is None:
            return jax.vmap(
                lambda pp, *ov: solve_fractions(
                    pp, objective=objective, **solve_kw, **dict(zip(names, ov))
                )
            )(p, *vals)
        return jax.vmap(
            lambda pp, lv, *ov: solve_fractions(
                pp, objective=objective, live=lv, **solve_kw,
                **dict(zip(names, ov)),
            )
        )(p, live_rows, *vals)

    # Unconstrained (risk-neutral) pre-solve: the allocation baseline.
    mean_obj = Objective.mean()
    f0, st0 = vsolve(params, mean_obj, live_rows=live)
    e0, v0 = st0.e_t, st0.var  # (S,) per-attempt moments at the mean split

    # Cross-stage bookkeeping runs on effective contributions; per-stage
    # solves stay at the per-attempt level they control.
    if stochastic:
        p_exec, n_mean, n_var = _stochastic_factors(dag)
        eff_e0, eff_v0 = effective_stage_moments(dag, e0, v0)
    else:
        eff_e0, eff_v0 = e0, v0

    through, crit_len = path_lengths(dag, eff_e0)
    crit = (
        through / jnp.maximum(crit_len, 1e-9)
        if critical_path_aware
        else jnp.ones_like(e0)
    )

    if objectives is not None:
        obj_tuple = as_stage_objectives(objectives, dag.num_stages)
        fracs = f0
        groups: dict = {}
        for i, o in enumerate(obj_tuple):
            groups.setdefault(o, []).append(i)
        for o, idx_list in groups.items():
            if o.kind == "mean":
                continue  # the presolve rows already minimize E[t]
            idx = jnp.asarray(tuple(idx_list))
            take = lambda x: x[idx]
            p_g = jax.tree_util.tree_map(take, params)
            lv_g = None if live is None else live[idx]
            if o.kind == "mean_var":
                ra = o.risk_aversion * crit[idx]
                if stochastic:
                    ra = ra * (p_exec * n_mean)[idx]
                f_g, _ = vsolve(p_g, o, live_rows=lv_g, risk_aversion=ra)
            elif o.kind == "var_budget":
                # Per-stage budgets constrain the stage's EFFECTIVE variance;
                # convert to the per-attempt budget the solve controls.
                b = jnp.full((len(idx_list),), o.var_budget, jnp.float32)
                if stochastic:
                    b = _attempt_var_budget(
                        b, e0[idx], p_exec[idx], n_mean[idx], n_var[idx]
                    )
                f_g, _ = vsolve(p_g, o, live_rows=lv_g, var_budget=b)
            else:  # deadline: the stage's own latency target
                d_g = jnp.full((len(idx_list),), o.deadline, jnp.float32)
                if stochastic:
                    d_g = d_g / n_mean[idx]  # each attempt gets its share
                f_g, _ = vsolve(p_g, o, live_rows=lv_g, deadline=d_g)
            fracs = fracs.at[idx].set(f_g)
        stats_obj = config.objective
    else:
        obj = config.objective
        stats_obj = obj
        if obj.kind == "mean":
            fracs = f0
        elif obj.kind == "mean_var":
            ra = obj.risk_aversion * crit  # (S,)
            if stochastic:
                ra = ra * p_exec * n_mean
            fracs, _ = vsolve(params, obj, live_rows=live, risk_aversion=ra)
        elif obj.kind == "var_budget":
            w = eff_v0 * crit + 1e-12
            budget = jnp.asarray(obj.var_budget, jnp.float32)
            b_s = budget * w / jnp.sum(w)  # effective-variance slices
            if stochastic:
                b_s = _attempt_var_budget(b_s, e0, p_exec, n_mean, n_var)
            solve_b = lambda b: vsolve(params, obj, live_rows=live, var_budget=b)
            fracs, st1 = solve_b(b_s)
            # Reallocation round: non-binding stages (v clearly below their
            # slice) donate their surplus to stages that clipped against
            # theirs — spend the risk budget where it actually buys expected
            # time.  A stage is donor OR receiver, never both, so the
            # re-solve slices still sum to <= the end-to-end budget.
            binding = st1.var >= 0.95 * b_s
            surplus = jnp.sum(
                jnp.where(binding, 0.0, jnp.maximum(b_s - st1.var, 0.0))
            )
            recv = binding.astype(jnp.float32) * w
            extra = surplus * recv / jnp.maximum(jnp.sum(recv), 1e-12)
            fracs, _ = solve_b(b_s + extra)
        else:  # deadline
            d = jnp.asarray(obj.deadline, jnp.float32)
            d_s = d * eff_e0 / jnp.maximum(through, 1e-9)  # path-wise slices
            if stochastic:
                d_s = d_s / n_mean  # per-attempt share of the stage's slice
            fracs, _ = vsolve(params, obj, live_rows=live, deadline=d_s)

        if stochastic:
            # Joint end-to-end refinement: keep it only if the composed
            # objective actually improves.
            with jax.named_scope("joint_refine"):
                refined = _joint_refine(dag, fracs, params, obj, config, live)
                sc_base = _dag_objective_score(
                    dag, fracs, params, obj, config.num_points
                )
                sc_ref = _dag_objective_score(
                    dag, refined, params, obj, config.num_points
                )
                fracs = jnp.where(sc_ref < sc_base, refined, fracs)

    stats = dag_stats(dag, fracs, params, stats_obj, num_points=config.num_points)
    return fracs, stats


def _attempt_var_budget(
    b_eff: Array, e0: Array, p_exec: Array, n_mean: Array, n_var: Array
) -> Array:
    """Invert the effective-variance transform at the allocation point.

    v_eff = p (E[N] v + Var[N] e^2) + p (1 - p) (E[N] e)^2, solved for the
    per-attempt variance v a stage's solve controls, holding the per-attempt
    mean at the presolve value ``e0``.  Floored at a tiny positive budget:
    an allocation smaller than the structural variance (rework/branch terms
    that no split can remove) still yields the stage's minimum-variance
    split rather than NaN.
    """
    v = (
        b_eff / jnp.maximum(p_exec, 1e-9)
        - n_var * e0 * e0
        - (1.0 - p_exec) * (n_mean * e0) ** 2
    ) / jnp.maximum(n_mean, 1e-9)
    return jnp.maximum(v, 1e-9)
