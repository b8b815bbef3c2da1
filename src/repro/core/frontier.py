"""Completion-time statistics of a partitioned workflow and the QoS frontier.

Implements Section 1 of the paper, generalized from 2 units to K units:

  P(t <= eps | f, Theta) = prod_k P(t_k <= eps | f_k, Theta_k)
  E(t)   = int_0^inf [1 - P(t <= eps)] d eps
  Var(t) = 2 int_0^inf eps [1 - P(t <= eps)] d eps - E(t)^2

with per-unit times t_k ~ N(f_k^alpha_k mu_k, (f_k^beta_k sigma_k)^2).
The (mu(f), sigma^2(f)) locus over the fraction simplex is parabola-like; its
Pareto-minimal subset is the efficient frontier used to pick the operating
point for a QoS target.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .distributions import normal_cdf

Array = jax.Array

DEFAULT_QUAD_POINTS = 1024


class UnitParams(NamedTuple):
    """Per-unit completion-time model parameters; leaves have shape (K,)."""

    mu: Array
    sigma: Array
    alpha: Array
    beta: Array

    @staticmethod
    def of(mu, sigma, alpha=None, beta=None) -> "UnitParams":
        mu = jnp.asarray(mu, jnp.float32)
        sigma = jnp.asarray(sigma, jnp.float32)
        one = jnp.ones_like(mu)
        return UnitParams(
            mu,
            sigma,
            one if alpha is None else jnp.asarray(alpha, jnp.float32),
            one if beta is None else jnp.asarray(beta, jnp.float32),
        )


def component_mean_std(fracs: Array, params: UnitParams) -> Tuple[Array, Array]:
    """Per-unit mean f^alpha mu and std f^beta sigma for fractions (..., K)."""
    f = jnp.maximum(fracs, 1e-9)
    mean = f**params.alpha * params.mu
    std = f**params.beta * params.sigma
    return mean, jnp.maximum(std, 1e-9)


def completion_cdf(eps: Array, fracs: Array, params: UnitParams) -> Array:
    """P(t <= eps | f, Theta): product of per-unit Normal CDFs.

    eps: (..., Q); fracs: (K,).  Returns (..., Q).
    """
    mean, std = component_mean_std(fracs, params)  # (K,)
    cdfs = normal_cdf(eps[..., None], mean, std)  # (..., Q, K)
    return jnp.prod(cdfs, axis=-1)


def _quad_grid(means: Array, stds: Array, num_points: int, dtype) -> Array:
    """Quadrature abscissae on [0, max(mean + 8 std)] — the survival
    integrand is exponentially small beyond."""
    upper = jnp.maximum(jnp.max(means + 8.0 * stds), 1e-6)
    return jnp.linspace(0.0, 1.0, num_points, dtype=dtype) * upper


def _moments_from_survival(eps: Array, surv: Array) -> Tuple[Array, Array]:
    """(E, Var) of a nonnegative variable from its survival function values."""
    e_t = jnp.trapezoid(surv, eps)
    e_t2 = 2.0 * jnp.trapezoid(eps * surv, eps)
    return e_t, jnp.maximum(e_t2 - e_t * e_t, 0.0)


def mean_var_completion(
    fracs: Array,
    params: UnitParams,
    num_points: int = DEFAULT_QUAD_POINTS,
) -> Tuple[Array, Array]:
    """E(t) and Var(t) of the max-completion time by trapezoid quadrature.

    Differentiable in ``fracs`` so the partitioner can use gradients.
    """
    mean, std = component_mean_std(fracs, params)
    eps = _quad_grid(mean, std, num_points, fracs.dtype)
    surv = 1.0 - completion_cdf(eps, fracs, params)  # (Q,)
    return _moments_from_survival(eps, surv)


# --------------------------------------------------------------------------
# stochastic stage transforms (conditional branches + rework loops)
# --------------------------------------------------------------------------
def mixture_moments(p: Array, mean: Array, var: Array) -> Tuple[Array, Array]:
    """Moments of ``Z = B * X`` with ``B ~ Bernoulli(p)`` independent of X.

    A conditionally-executed workflow stage contributes its makespan only
    when its path indicator fires; the law of total mean/variance over the
    Bernoulli activation gives

      E[Z]   = p E[X]
      Var[Z] = p Var[X] + p (1 - p) E[X]^2

    (condition on B: the mean-of-variances is ``p Var[X]``, the
    variance-of-means is that of a two-point {0, E[X]} distribution).
    Broadcasts elementwise; exact — no distributional approximation — so
    the MC oracle (``repro.sim``) pins it to sampling noise.  ``p = 1`` is
    an exact identity (``1*x == x``, ``v + 0.0 == v`` bitwise).

    >>> import jax.numpy as jnp
    >>> e, v = mixture_moments(jnp.float32(0.25), jnp.float32(8.0),
    ...                        jnp.float32(4.0))
    >>> float(e), float(v)                # 0.25*8, 0.25*4 + 0.25*0.75*64
    (2.0, 13.0)
    """
    e = p * mean
    v = p * var + p * (1.0 - p) * (mean * mean)
    return e, v


def truncated_geometric_moments(
    success_prob: Array,
    max_attempts,
    *,
    max_support: Optional[int] = None,
) -> Tuple[Array, Array]:
    """(E[N], Var[N]) of ``N = min(Geometric(q), R)`` attempt counts.

    A rework loop retries a stage until it succeeds (per-attempt success
    probability ``q``) or hits the retry cap ``R = max_attempts``; the pmf is
    ``P(N=n) = (1-q)^(n-1) q`` for ``n < R`` and the whole surviving tail
    ``(1-q)^(R-1)`` collapses onto ``n = R``.  Moments are computed exactly
    from the pmf over the static support ``1..max_support`` (``max_attempts``
    may be a traced per-stage array bounded by the static ``max_support``),
    so this jits and differentiates through ``q``.

    The untruncated limits are recovered as R grows: ``E[N] -> 1/q``,
    ``Var[N] -> (1-q)/q^2``.  ``q = 1`` (or ``R = 1``) puts all mass on
    ``N = 1`` exactly: E[N] == 1.0 and Var[N] == 0.0 bitwise, which is what
    keeps zero-rework topologies on the deterministic code path's numbers.

    >>> import jax.numpy as jnp
    >>> e_n, v_n = truncated_geometric_moments(jnp.float32(0.5), 30)
    >>> round(float(e_n), 4), round(float(v_n), 4)   # ~1/q, ~(1-q)/q^2
    (2.0, 2.0)
    >>> e_1, v_1 = truncated_geometric_moments(jnp.float32(0.5), 1)
    >>> float(e_1), float(v_1)                       # cap 1 = no rework
    (1.0, 0.0)
    """
    q = jnp.asarray(success_prob, jnp.float32)
    if max_support is None:
        if isinstance(max_attempts, int):
            max_support = max_attempts
        elif isinstance(max_attempts, (tuple, list)):
            max_support = int(max(max_attempts))
        else:
            raise ValueError(
                "max_support is required when max_attempts is a traced array"
            )
    caps = jnp.asarray(max_attempts, jnp.float32)[..., None]
    n = jnp.arange(1, max_support + 1, dtype=jnp.float32)  # static support
    fail = 1.0 - q[..., None]
    geometric = fail ** (n - 1.0) * q[..., None]
    tail = fail ** (caps - 1.0)  # all surviving mass collapses onto n == cap
    pmf = jnp.where(n < caps, geometric, jnp.where(n == caps, tail, 0.0))
    e_n = jnp.sum(n * pmf, axis=-1)
    e_n2 = jnp.sum(n * n * pmf, axis=-1)
    return e_n, jnp.maximum(e_n2 - e_n * e_n, 0.0)


def compound_sum_moments(
    n_mean: Array, n_var: Array, mean: Array, var: Array
) -> Tuple[Array, Array]:
    """Moments of ``T = sum_{i=1}^N X_i`` (i.i.d. X independent of N).

    The compound-sum (Wald) identities:

      E[T]   = E[N] E[X]
      Var[T] = E[N] Var[X] + Var[N] E[X]^2

    Exact for any attempt-count distribution — pair with
    :func:`truncated_geometric_moments` for geometric rework loops.
    ``(E[N], Var[N]) = (1, 0)`` is a bitwise identity.

    >>> import jax.numpy as jnp
    >>> e, v = compound_sum_moments(jnp.float32(2.0), jnp.float32(2.0),
    ...                             jnp.float32(3.0), jnp.float32(0.5))
    >>> float(e), float(v)                 # 2*3, 2*0.5 + 2*9
    (6.0, 19.0)
    """
    return n_mean * mean, n_mean * var + n_var * (mean * mean)


def stochastic_stage_moments(
    stage_means: Array,
    stage_vars: Array,
    *,
    exec_probs: Optional[Array] = None,
    success_probs: Optional[Array] = None,
    max_retries=None,
    max_support: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Effective stage-duration moments under rework loops + branch activation.

    Transforms per-execution ("one attempt, stage taken") makespan moments
    into the moments of what the stage actually contributes to the workflow:
    geometric rework first (the loop repeats the attempt — ``success_probs``
    is the per-attempt success probability, i.e. 1 - rework probability),
    Bernoulli path activation second (a skipped stage skips ALL its retries).
    Both transforms are exact in the moments, so chain compositions of the
    result stay exact; only fork/join max-composition introduces the usual
    moment-matching approximation.

    >>> import jax.numpy as jnp
    >>> e, v = stochastic_stage_moments(
    ...     jnp.asarray([3.0, 5.0]), jnp.asarray([0.5, 1.0]),
    ...     exec_probs=jnp.asarray([1.0, 0.5]),
    ...     success_probs=jnp.asarray([0.5, 1.0]), max_retries=(30, 1))
    >>> [round(float(x), 3) for x in e]      # stage 0: ~2 attempts of 3
    [6.0, 2.5]
    """
    e, v = stage_means, stage_vars
    if success_probs is not None:
        if max_retries is None:
            raise ValueError("success_probs requires max_retries")
        n_mean, n_var = truncated_geometric_moments(
            success_probs, max_retries, max_support=max_support
        )
        e, v = compound_sum_moments(n_mean, n_var, e, v)
    if exec_probs is not None:
        e, v = mixture_moments(exec_probs, e, v)
    return e, v


# --------------------------------------------------------------------------
# stage composition (multi-stage workflow DAGs)
# --------------------------------------------------------------------------
def serial_moments(stage_means: Array, stage_vars: Array) -> Tuple[Array, Array]:
    """Serial (chain) composition of stage completion moments.

    A pipeline's end-to-end time is the SUM of its stage makespans (stage
    s+1 starts when stage s finishes), so with independent stage times the
    mean and variance both add — the companion paper's sequential-channel
    composition.  ``stage_means``/``stage_vars`` are (S,) (or (S, ...) for
    batched composition over a trailing axis).

    >>> import jax.numpy as jnp
    >>> e, v = serial_moments(jnp.asarray([3.0, 2.0]), jnp.asarray([0.4, 0.1]))
    >>> float(e), float(v)
    (5.0, 0.5)
    """
    return jnp.sum(stage_means, axis=0), jnp.sum(stage_vars, axis=0)


def parallel_max_moments(
    branch_means: Array,
    branch_vars: Array,
    num_points: int = DEFAULT_QUAD_POINTS,
) -> Tuple[Array, Array]:
    """Moments of the max over parallel branches by survival quadrature.

    Each branch's completion time is moment-matched to a Normal; the max of
    independent branches then has CDF ``prod_b Phi((eps - m_b)/s_b)``, and
    E/Var follow from the same survival-function integration used for the
    within-stage worker max (:func:`mean_var_completion`).  Branches that
    share ancestors are treated as independent (the classic PERT
    approximation) — the induced positive correlation means the true E[max]
    is slightly LOWER than reported, so the composition errs conservative.

    >>> import jax.numpy as jnp
    >>> e, v = parallel_max_moments(
    ...     jnp.asarray([3.0, 3.0]), jnp.asarray([0.25, 0.25]))
    >>> bool(e > 3.0)   # E[max of two noisy branches] exceeds either mean
    True
    >>> e0, _ = parallel_max_moments(jnp.asarray([5.0]), jnp.asarray([1e-9]))
    >>> bool(abs(e0 - 5.0) < 0.01)  # single near-deterministic branch
    True
    """
    std = jnp.sqrt(jnp.maximum(branch_vars, 1e-18))
    eps = _quad_grid(branch_means, std, num_points, jnp.float32)
    cdfs = normal_cdf(eps[:, None], branch_means, std)  # (Q, B)
    surv = 1.0 - jnp.prod(cdfs, axis=-1)
    return _moments_from_survival(eps, surv)


def dag_completion_moments(
    preds: Tuple[Tuple[int, ...], ...],
    stage_means: Array,
    stage_vars: Array,
    *,
    num_points: int = DEFAULT_QUAD_POINTS,
) -> Tuple[Array, Array]:
    """End-to-end (E, Var) of a stage DAG by topological reduction.

    ``preds`` is the static topology: ``preds[i]`` lists the stages that must
    finish before stage i starts, with every predecessor index < i (stages
    topologically numbered — ``repro.sched.WorkflowDAG`` guarantees this).
    Each stage's finish time is tracked as a moment-matched Normal: a stage's
    start is the max over its predecessors' finishes
    (:func:`parallel_max_moments`), its finish adds its own makespan moments
    (:func:`serial_moments` pairwise), and the DAG completes at the max over
    sink stages.  A serial chain reduces exactly to summed moments; parallel
    branches compose by quadrature over the per-branch survival functions.

    >>> import jax.numpy as jnp
    >>> chain = ((), (0,), (1,))                   # 0 -> 1 -> 2
    >>> e, v = dag_completion_moments(
    ...     chain, jnp.asarray([1.0, 2.0, 3.0]), jnp.asarray([0.1, 0.1, 0.1]))
    >>> round(float(e), 4), round(float(v), 4)     # chain == summed moments
    (6.0, 0.3)
    >>> diamond = ((), (0,), (0,), (1, 2))         # 0 -> {1, 2} -> 3
    >>> e_d, _ = dag_completion_moments(
    ...     diamond, jnp.asarray([1.0, 2.0, 2.0, 1.0]),
    ...     jnp.asarray([0.1, 0.2, 0.2, 0.1]))
    >>> bool(e_d > 4.0)   # E[max] of the noisy parallel arms adds a premium
    True
    """
    s = len(preds)
    fin_e: list = [None] * s
    fin_v: list = [None] * s
    for i in range(s):
        ps = preds[i]
        if not ps:
            start_e = jnp.asarray(0.0, jnp.float32)
            start_v = jnp.asarray(0.0, jnp.float32)
        elif len(ps) == 1:
            start_e, start_v = fin_e[ps[0]], fin_v[ps[0]]
        else:
            start_e, start_v = parallel_max_moments(
                jnp.stack([fin_e[p] for p in ps]),
                jnp.stack([fin_v[p] for p in ps]),
                num_points,
            )
        fin_e[i] = start_e + stage_means[i]
        fin_v[i] = start_v + stage_vars[i]
    has_succ = {p for pp in preds for p in pp}
    sinks = [i for i in range(s) if i not in has_succ]
    if len(sinks) == 1:
        return fin_e[sinks[0]], fin_v[sinks[0]]
    return parallel_max_moments(
        jnp.stack([fin_e[i] for i in sinks]),
        jnp.stack([fin_v[i] for i in sinks]),
        num_points,
    )


def sweep_two_way(
    params: UnitParams,
    num_f: int = 201,
    num_points: int = DEFAULT_QUAD_POINTS,
) -> Tuple[Array, Array, Array]:
    """The paper's Fig 1/2 curves: (f_grid, mu(f), sigma^2(f)) for K=2."""
    f_grid = jnp.linspace(1e-3, 1.0 - 1e-3, num_f, dtype=jnp.float32)

    def one(f):
        fracs = jnp.stack([f, 1.0 - f])
        return mean_var_completion(fracs, params, num_points)

    mu_f, var_f = jax.vmap(one)(f_grid)
    return f_grid, mu_f, var_f


def pareto_mask(mu_f: Array, var_f: Array) -> Array:
    """Efficient frontier: points not dominated in (mu, var) (both minimized)."""
    dominated = jnp.any(
        (mu_f[None, :] <= mu_f[:, None])
        & (var_f[None, :] <= var_f[:, None])
        & ((mu_f[None, :] < mu_f[:, None]) | (var_f[None, :] < var_f[:, None])),
        axis=1,
    )
    return ~dominated

