"""Pluggable partitioning objectives over the completion-time frontier.

The companion paper ("Partitioning Uncertain Workflows") frames the split
choice as an objective over the (mean, variance) frontier.  One ``Objective``
value encodes that choice everywhere — the K-simplex optimizer
(``sched.solve_fractions``), the two-way frontier pick
(``optimal_two_way_fraction``, below), microbatch quantization, and the
serve path.

An ``Objective`` is a frozen, hashable dataclass, so it rides through
``jax.jit`` as a static argument; scores are pure jnp and differentiable
(``smooth=True`` swaps hard constraints/indicators for their soft relaxations
so the simplex optimizer can follow gradients).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.frontier import (
    DEFAULT_QUAD_POINTS,
    UnitParams,
    completion_cdf,
    mean_var_completion,
    sweep_two_way,
)

Array = jax.Array

# Hard-constraint violations are scored BIG + violation instead of inf so that
# argmin still orders infeasible points (and never returns NaN from inf-inf).
_BIG = 1e9


@dataclasses.dataclass(frozen=True)
class Objective:
    """What "best split" means.  Lower score is better.

    kind:
      "mean"        — E[t]                          (fastest expected)
      "mean_var"    — E[t] + risk_aversion * Var[t] (risk-sensitive)
      "var_budget"  — min E[t]  s.t.  Var[t] <= var_budget
      "deadline"    — max P(t <= deadline)          (QoS quantile target)

    One value encodes the choice for every consumer — the simplex solver,
    the two-way frontier sweep, quantization, and the serve path:

    >>> obj = Objective.mean_var(0.5)
    >>> float(obj.score_moments(10.0, 4.0))        # E + 0.5 * Var
    12.0
    >>> feasible = Objective.variance_budget(5.0)
    >>> bool(feasible.score_moments(10.0, 4.0) < feasible.score_moments(9.0, 6.0))
    True
    >>> Objective.deadline_quantile(30.0).needs_cdf()  # moment form not enough
    True
    """

    kind: str = "mean"
    risk_aversion: float = 0.0
    var_budget: float = math.inf
    deadline: float = 0.0

    def __post_init__(self):
        if self.kind not in ("mean", "mean_var", "var_budget", "deadline"):
            raise ValueError(f"unknown objective kind {self.kind!r}")

    # -- constructors --------------------------------------------------------
    @staticmethod
    def mean() -> "Objective":
        return Objective(kind="mean")

    @staticmethod
    def mean_var(risk_aversion: float) -> "Objective":
        return Objective(kind="mean_var", risk_aversion=float(risk_aversion))

    @staticmethod
    def variance_budget(var_budget: float) -> "Objective":
        return Objective(kind="var_budget", var_budget=float(var_budget))

    @staticmethod
    def deadline_quantile(deadline: float) -> "Objective":
        return Objective(kind="deadline", deadline=float(deadline))

    # -- scoring -------------------------------------------------------------
    def score_moments(self, e_t: Array, var: Array, *, smooth: bool = False) -> Array:
        """Score from completion-time moments alone (broadcasts elementwise).

        Only valid for the moment-based kinds; "deadline" needs the full CDF —
        use :func:`evaluate` (or :meth:`needs_cdf` to dispatch).
        """
        return score_moments_dynamic(
            self.kind, e_t, var, self.risk_aversion, self.var_budget,
            smooth=smooth,
        )

    def needs_cdf(self) -> bool:
        return self.kind == "deadline"


def as_stage_objectives(objectives, num_stages: int) -> tuple:
    """Normalize a per-stage objective spec to a validated tuple.

    Accepts a single ``Objective`` (broadcast to every stage) or a sequence
    with exactly one entry per stage.  The result is a plain tuple of frozen
    ``Objective`` values, so it is hashable and rides through ``jax.jit`` as
    a static argument (``sched.propose_dag(objectives=...)``,
    ``sched.quantize_dag_fractions(objectives=...)``).

    >>> objs = as_stage_objectives(Objective.mean(), 2)
    >>> len(objs), objs[0].kind
    (2, 'mean')
    >>> len(as_stage_objectives((Objective.mean(), Objective.mean_var(0.5)), 2))
    2
    """
    if isinstance(objectives, Objective):
        return (objectives,) * num_stages
    objectives = tuple(objectives)
    if len(objectives) != num_stages:
        raise ValueError(
            f"need one objective per stage: got {len(objectives)} "
            f"for {num_stages} stages"
        )
    for o in objectives:
        if not isinstance(o, Objective):
            raise TypeError(f"expected Objective, got {type(o).__name__}")
    return objectives


def score_moments_dynamic(
    kind: str,
    e_t: Array,
    var: Array,
    risk_aversion,
    var_budget,
    *,
    smooth: bool = False,
) -> Array:
    """Moment-based scoring with the floats as (possibly traced) values.

    ``Objective.score_moments`` bakes its floats in as jit-static constants —
    right for the scheduler, whose objective rarely changes.  Callers that
    sweep the risk/budget parameter (e.g. tracing a tradeoff curve through
    ``optimal_two_way_fraction``) use this form so only ``kind`` is static
    and every parameter value reuses one compilation.
    """
    if kind == "mean":
        return e_t
    if kind == "mean_var":
        return e_t + risk_aversion * var
    if kind == "var_budget":
        excess = var - var_budget
        if smooth:
            # softplus barrier keeps the score differentiable; the sharp
            # scale makes the feasible region's boundary steep.
            return e_t + jax.nn.softplus(20.0 * excess)
        return jnp.where(excess <= 0, e_t, _BIG + excess)
    raise ValueError(f"objective {kind!r} is not moment-based")


def evaluate(
    objective: Objective,
    fracs: Array,
    params,
    *,
    num_points: int = 512,
    smooth: bool = False,
    risk_aversion=None,
    var_budget=None,
    deadline=None,
) -> Array:
    """Score one fraction vector (K,) on the simplex.  Lower is better.

    Pure and differentiable in ``fracs``; ``objective`` must be static under
    jit.  ``params`` is a ``frontier.UnitParams``.

    ``risk_aversion`` / ``var_budget`` / ``deadline``, when given, override
    the objective's static floats with (possibly traced) values — only the
    KIND stays jit-static.  This is how the DAG partitioner hands each stage
    its own slice of a shared risk budget or end-to-end deadline without one
    compilation per stage (``sched.dag.propose_dag`` vmaps over stages).
    """
    if objective.needs_cdf():
        d = objective.deadline if deadline is None else deadline
        p_meet = completion_cdf(jnp.asarray(d, fracs.dtype), fracs, params)
        if smooth:
            return -jnp.log(jnp.maximum(p_meet, 1e-12))
        return -p_meet
    e_t, var = mean_var_completion(fracs, params, num_points)
    return score_moments_dynamic(
        objective.kind,
        e_t,
        var,
        objective.risk_aversion if risk_aversion is None else risk_aversion,
        objective.var_budget if var_budget is None else var_budget,
        smooth=smooth,
    )


def optimal_two_way_fraction(
    params: UnitParams,
    *,
    objective: Objective = Objective.mean(),
    num_f: int = 201,
    num_points: int = DEFAULT_QUAD_POINTS,
) -> Tuple[Array, Array, Array]:
    """Pick f on the K=2 frontier: the best of ``num_f`` points for ``objective``.

    Only the objective's *kind* is jit-static: its floats are passed traced,
    so sweeping ``risk_aversion``, ``var_budget`` or ``deadline`` reuses one
    compilation.  Returns (f*, mu(f*), sigma^2(f*)).
    """
    return _optimal_two_way(
        params,
        jnp.asarray(objective.risk_aversion, jnp.float32),
        jnp.asarray(objective.var_budget, jnp.float32),
        jnp.asarray(objective.deadline, jnp.float32),
        kind=objective.kind,
        num_f=num_f,
        num_points=num_points,
    )


@functools.partial(jax.jit, static_argnames=("kind", "num_f", "num_points"))
def _optimal_two_way(
    params: UnitParams,
    risk_aversion: Array,
    var_budget: Array,
    deadline: Array,
    *,
    kind: str,
    num_f: int,
    num_points: int,
) -> Tuple[Array, Array, Array]:
    f_grid, mu_f, var_f = sweep_two_way(params, num_f, num_points)
    if kind == "deadline":
        score = jax.vmap(
            lambda f: -completion_cdf(deadline, jnp.stack([f, 1.0 - f]), params)
        )(f_grid)
    else:
        score = score_moments_dynamic(kind, mu_f, var_f, risk_aversion, var_budget)
    idx = jnp.argmin(score)
    return f_grid[idx], mu_f[idx], var_f[idx]
