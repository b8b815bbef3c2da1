"""Empirical-Bayes fleet hyperprior: cold-start, transfer, drift scoring.

The paper infers each processing unit's characteristics independently, so
every worker that joins the fleet starts from the same vague global prior
and burns its first N observations re-learning what the fleet already
knows — the costly-experimentation problem the paper set out to avoid,
re-created at the fleet level.  This module pools statistical strength
across the fleet (the Lotaru local-estimation-with-transfer argument)
without touching the per-worker estimator:

  * :func:`fit_hyperprior` — fit fleet-level hyperparameters from the
    current per-worker posteriors by moment matching: a pooled
    Normal-Gamma ``(mu0, kappa0, a0, b0)`` over each worker's ``(mu,
    lambda)`` and pooled Beta summaries of the ``(K, 2, G)`` exponent
    posteriors (the per-worker Beta moment fits ARE the grid's first two
    moments, Eqs 12-18, so pooling them pools the grids).  Pure,
    jit/vmap-compatible; the per-shard reduction is a handful of scalar
    sums, so under ``shard_map`` the refit is one ``psum`` of O(1)
    sufficient statistics (:func:`hyper_stats` / :func:`hyper_from_stats`).
  * :func:`shrink` — blend each worker's posterior toward the fleet prior
    with an effective-sample-size weight ``w_k = tau / (tau + ess_k)``:
    a cold worker (ess 0) lands exactly on the fleet prior, a mature
    worker keeps its own data, and weight 0 is a bitwise no-op.
  * :func:`surprise` — score each worker's posterior point estimates
    against the pooled prior: the log marginal-likelihood ratio between
    the hyperprior evaluated at its own typical parameters and at the
    worker's, a per-worker ``(K,)`` device-resident statistic that grows
    as a worker's posterior escapes the pooled prior.  Its distribution
    under the null does not depend on which worker you ask, which is what
    makes an online-calibrated gate over it fleet-size-invariant
    (``repro.serve.gate``) — unlike a fixed threshold on the
    max-over-workers drift, whose null level grows with K.

``shrink`` and ``surprise`` are strictly per-worker (no cross-fleet ops),
so both run per-shard under ``shard_map`` unchanged; only the O(1)-sized
hyperparameters are replicated.  Derivations in ``docs/hierarchy.md``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.distributions import (
    EPS,
    TINY,
    beta_logpdf,
    gamma_logpdf,
    normal_logpdf,
)
from repro.core.gibbs import GibbsState
from repro.core.moments import BetaParams
from repro.core.posterior import NormalGammaParams
from repro.core.sharding import ShardingConfig, shard_fleet_call

Array = jax.Array

# The Normal-Gamma pseudo-count floor all per-worker chains start from
# (``NormalGammaParams.default`` / ``fit_fleet``): nu0 = 1.  Effective
# sample size is measured as observations accumulated past that floor.
_NU_INIT = 1.0
# Default pseudo-observation strength of the fleet prior in ``shrink``:
# a worker needs ~tau of its own observations to outvote the fleet.
DEFAULT_STRENGTH = 8.0


class Hyperprior(NamedTuple):
    """Fleet-level hyperparameters; a tiny (all-scalar) pytree.

    ``ng`` is the pooled Normal-Gamma over each worker's ``(mu, lambda)``
    — its ``(mu0, kappa0, nu0, psi0)`` are the fleet's ``(mu0, kappa0,
    a0, b0)`` — and ``alpha_prior`` / ``beta_prior`` are the pooled Beta
    summaries of the per-worker exponent posteriors.  ``n_workers`` is
    the (masked) worker count the fit pooled, for observability.
    """

    ng: NormalGammaParams
    alpha_prior: BetaParams
    beta_prior: BetaParams
    n_workers: Array  # float32 scalar


class HyperStats(NamedTuple):
    """Per-shard sufficient statistics of the hyperprior refit.

    Thirteen scalars — sums over (masked) workers — so a sharded refit
    moves O(1) data per shard: ``psum`` these, then :func:`hyper_from_stats`.
    ``m*``: posterior means of mu; ``l*``: posterior means of lambda;
    ``a*`` / ``b*``: posterior means of the alpha / beta exponents; the
    ``v*`` entries are the summed *within-worker* posterior variances that
    keep the pooled prior honest about estimation noise.
    """

    n: Array
    m1: Array
    m2: Array
    vm: Array
    l1: Array
    l2: Array
    vl: Array
    a1: Array
    a2: Array
    va: Array
    b1: Array
    b2: Array
    vb: Array


def hyper_init(mu_guess: float = 1.0) -> Hyperprior:
    """The global prior as a degenerate hyperprior (nothing pooled yet)."""
    return Hyperprior(
        ng=NormalGammaParams.default(mu_guess),
        alpha_prior=BetaParams.default(),
        beta_prior=BetaParams.default(),
        n_workers=jnp.zeros((), jnp.float32),
    )


def _beta_mean_var(p: BetaParams) -> Tuple[Array, Array]:
    s = p.a + p.b
    mean = p.a / jnp.maximum(s, TINY)
    var = p.a * p.b / jnp.maximum(s * s * (s + 1.0), TINY)
    return mean, var


def hyper_stats(fleet: GibbsState, mask: Optional[Array] = None) -> HyperStats:
    """Sufficient statistics of the refit from a (K, ...)-leaf fleet state.

    ``mask`` optionally excludes workers (shard-padding dummies, evicted
    rows) with weight 0.  Strictly a per-worker map followed by a sum over
    the fleet axis, so per-shard calls compose by addition (``psum``).
    """
    ng = fleet.ng
    m_k = jnp.asarray(ng.mu0, jnp.float32)
    lam_k = jnp.asarray(ng.nu0 / jnp.maximum(ng.psi0, TINY), jnp.float32)
    # Within-worker posterior variances: Var[mu] = psi/(kappa (nu-1))
    # (guarded for vague nu), Var[lambda] = nu/psi^2.
    vmu_k = ng.psi0 / jnp.maximum(ng.kappa0 * jnp.maximum(ng.nu0 - 1.0, 0.1), TINY)
    vlam_k = ng.nu0 / jnp.maximum(ng.psi0 * ng.psi0, TINY)
    a_mean, a_var = _beta_mean_var(fleet.alpha_prior)
    b_mean, b_var = _beta_mean_var(fleet.beta_prior)

    w = jnp.ones_like(m_k) if mask is None else jnp.asarray(mask, m_k.dtype)
    s = lambda x: jnp.sum(w * x, axis=-1)
    return HyperStats(
        n=s(jnp.ones_like(m_k)),
        m1=s(m_k), m2=s(m_k * m_k), vm=s(vmu_k),
        l1=s(lam_k), l2=s(lam_k * lam_k), vl=s(vlam_k),
        a1=s(a_mean), a2=s(a_mean * a_mean), va=s(a_var),
        b1=s(b_mean), b2=s(b_mean * b_mean), vb=s(b_var),
    )


def _pool_beta(m1: Array, m2: Array, vw: Array, n: Array) -> BetaParams:
    """Moment-match a Beta to a population of Beta posteriors.

    Total predictive variance = between-worker spread of the posterior
    means + mean within-worker variance (law of total variance), so a
    fleet of vague posteriors yields a vague pool, never false confidence.
    """
    mean = jnp.clip(m1 / n, EPS, 1.0 - EPS)
    var = jnp.maximum(m2 / n - mean * mean, 0.0) + vw / n
    var = jnp.maximum(var, 1e-6)
    conc = jnp.clip(mean * (1.0 - mean) / var - 1.0, 0.5, 1e4)
    return BetaParams(a=mean * conc, b=(1.0 - mean) * conc)


def hyper_from_stats(stats: HyperStats) -> Hyperprior:
    """Moment-match the pooled hyperprior from (psum-ed) sufficient stats.

    * ``mu0 = mean_k E[mu_k]``; ``kappa0`` solves ``Var(mu | lambda) =
      1/(kappa0 lambda_bar) = V_mu`` where ``V_mu`` is the fleet's total
      (between + within) mu variance — a tight fleet pools hard, a
      heterogeneous fleet stays honest about its spread;
    * ``Gamma(a0, b0)`` over lambda matches the fleet's mean and total
      variance of the per-worker precision means, with ``b0 = a0 /
      lambda_bar`` so clipping ``a0`` never biases ``E[lambda]``;
    * the exponent pools are Beta moment matches of the per-worker Beta
      posteriors (themselves the Eqs 12-15 moment fits of the grids).
    """
    n = jnp.maximum(stats.n, 1.0)
    mu0 = stats.m1 / n
    v_mu = (
        jnp.maximum(stats.m2 / n - mu0 * mu0, 0.0) + stats.vm / n + 1e-8
    )
    lam_bar = jnp.maximum(stats.l1 / n, TINY)
    kappa0 = jnp.clip(1.0 / (v_mu * lam_bar), 1e-3, 1e6)
    v_lam = (
        jnp.maximum(stats.l2 / n - lam_bar * lam_bar, 0.0)
        + stats.vl / n + 1e-8
    )
    a0 = jnp.clip(lam_bar * lam_bar / v_lam, 0.51, 1e6)
    b0 = a0 / lam_bar
    return Hyperprior(
        ng=NormalGammaParams(
            mu0=jnp.asarray(mu0, jnp.float32),
            kappa0=jnp.asarray(kappa0, jnp.float32),
            nu0=jnp.asarray(a0, jnp.float32),
            psi0=jnp.asarray(b0, jnp.float32),
        ),
        alpha_prior=_pool_beta(stats.a1, stats.a2, stats.va, n),
        beta_prior=_pool_beta(stats.b1, stats.b2, stats.vb, n),
        n_workers=jnp.asarray(stats.n, jnp.float32),
    )


def _fit_hyperprior_body(
    fleet: GibbsState,
    mask: Optional[Array] = None,
    axis_name: Optional[str] = None,
) -> Hyperprior:
    stats = hyper_stats(fleet, mask)
    if axis_name is not None:
        stats = jax.lax.psum(stats, axis_name)
    return hyper_from_stats(stats)


@functools.partial(jax.jit, static_argnames=("axis_name",))
def fit_hyperprior(
    fleet: GibbsState,
    mask: Optional[Array] = None,
    *,
    axis_name: Optional[str] = None,
) -> Hyperprior:
    """Empirical-Bayes refit of the fleet hyperprior from per-worker posteriors.

    Pure and jit/vmap-compatible; hand it the ``gibbs`` leaf of a
    ``SchedulerState`` (leaves ``(K, ...)``).  Inside a ``shard_map``-ped
    program pass ``axis_name`` and the sufficient statistics are ``psum``-ed
    across shards — the refit then moves 13 scalars per shard, never a
    K-sized array (:func:`fit_hyperprior_sharded` wraps exactly this).
    """
    return _fit_hyperprior_body(fleet, mask, axis_name)


def fit_hyperprior_sharded(
    fleet: GibbsState,
    sharding: ShardingConfig,
    mask: Optional[Array] = None,
) -> Hyperprior:
    """The refit as one ``shard_map``-ped program over the fleet mesh.

    Each shard reduces its K/n_shards workers to 13 scalars, one ``psum``
    combines them, and every shard returns the identical (replicated)
    hyperprior.  K not divisible by the shard count is padded with
    mask-0 dummy workers, which contribute nothing to any statistic.
    """
    from jax.sharding import PartitionSpec as P

    k = jax.tree_util.tree_leaves(fleet)[0].shape[0]
    m = jnp.ones((k,), jnp.float32) if mask is None else jnp.asarray(mask)
    pad = sharding.pad(k)
    if pad:
        from repro.core.sharding import pad_fleet_axis

        fleet = pad_fleet_axis(fleet, pad)
        m = jnp.concatenate([m, jnp.zeros((pad,), m.dtype)])

    # NOTE: the body must stay unjitted and the eval_shape axis-free — a
    # psum traced outside the shard_map (eval_shape runs on full shapes,
    # no mesh context) raises "unbound axis name".
    fn = lambda fl, mm: _fit_hyperprior_body(fl, mm, sharding.axis)
    spec_of = lambda tree: jax.tree_util.tree_map(
        lambda _: P(sharding.axis), tree
    )
    out_spec = jax.tree_util.tree_map(
        lambda _: P(), jax.eval_shape(_fit_hyperprior_body, fleet, m)
    )
    return jax.shard_map(
        fn,
        mesh=sharding.mesh,
        in_specs=(spec_of(fleet), P(sharding.axis)),
        out_specs=out_spec,
        check_vma=False,
    )(fleet, m)


# --------------------------------------------------------------------------
# shrinkage
# --------------------------------------------------------------------------
def effective_sample_size(fleet: GibbsState) -> Array:
    """Observations each worker's posterior has absorbed, (K,).

    The Normal-Gamma ``nu`` grows by n/2 per batch from its ``nu0 = 1``
    birth value (and decays under power-prior forgetting), so ``2 (nu -
    1)`` counts the evidence currently alive in the posterior — exactly
    the quantity shrinkage should weigh against the fleet prior.
    """
    return jnp.maximum(2.0 * (jnp.asarray(fleet.ng.nu0) - _NU_INIT), 0.0)


def shrinkage_weight(
    fleet: GibbsState, strength: float = DEFAULT_STRENGTH
) -> Array:
    """Fleet-prior weight ``w = tau / (tau + ess)`` per worker, (K,) in [0, 1]."""
    tau = jnp.asarray(strength, jnp.float32)
    return tau / (tau + effective_sample_size(fleet))


def _log_blend(own: Array, pool: Array, w: Array) -> Array:
    """Geometric interpolation for positive scale/pseudo-count parameters."""
    return jnp.exp(
        (1.0 - w) * jnp.log(jnp.maximum(own, TINY))
        + w * jnp.log(jnp.maximum(pool, TINY))
    )


def _shrink_body(fleet: GibbsState, hyper: Hyperprior, w: Array) -> GibbsState:
    """Blend one shard's workers toward the (replicated) fleet prior."""
    guard = lambda own, blended: jnp.where(w > 0.0, blended, own)
    ng, h = fleet.ng, hyper.ng
    new_ng = NormalGammaParams(
        mu0=guard(ng.mu0, ng.mu0 + w * (h.mu0 - ng.mu0)),
        kappa0=guard(ng.kappa0, _log_blend(ng.kappa0, h.kappa0, w)),
        nu0=guard(ng.nu0, _log_blend(ng.nu0, h.nu0, w)),
        psi0=guard(ng.psi0, _log_blend(ng.psi0, h.psi0, w)),
    )
    blend_beta = lambda own, pool: BetaParams(
        a=guard(own.a, _log_blend(own.a, pool.a, w)),
        b=guard(own.b, _log_blend(own.b, pool.b, w)),
    )
    # The chain's current samples feed the next sweep's Normal-Gamma
    # weights (f^{alpha-2beta}), so a cold worker's wild prior draws are
    # pulled to the fleet's typical parameters along with its prior.
    lam_pool = h.nu0 / jnp.maximum(h.psi0, TINY)
    a_pool, _ = _beta_mean_var(hyper.alpha_prior)
    b_pool, _ = _beta_mean_var(hyper.beta_prior)
    return fleet._replace(
        ng=new_ng,
        alpha_prior=blend_beta(fleet.alpha_prior, hyper.alpha_prior),
        beta_prior=blend_beta(fleet.beta_prior, hyper.beta_prior),
        mu=guard(fleet.mu, fleet.mu + w * (h.mu0 - fleet.mu)),
        lam=guard(fleet.lam, _log_blend(fleet.lam, lam_pool, w)),
        alpha=guard(
            fleet.alpha,
            jnp.clip(fleet.alpha + w * (a_pool - fleet.alpha), EPS, 1.0 - EPS),
        ),
        beta=guard(
            fleet.beta,
            jnp.clip(fleet.beta + w * (b_pool - fleet.beta), EPS, 1.0 - EPS),
        ),
    )


def shrink(
    fleet: GibbsState,
    hyper: Hyperprior,
    weight: Optional[Array] = None,
    *,
    strength: float = DEFAULT_STRENGTH,
    sharding: Optional[ShardingConfig] = None,
) -> GibbsState:
    """Blend each worker's posterior toward the fleet prior; pure, jittable.

    ``weight`` (scalar or (K,)) overrides the effective-sample-size rule
    ``w = strength / (strength + ess)``.  Properties the tests pin:

      * ``weight=0`` is a bitwise no-op on every leaf (cheap to call
        unconditionally);
      * a cold worker (ess 0) lands exactly on the fleet hyperprior;
      * a mature worker (ess >> strength) barely moves.

    The blend is strictly per-worker, so with ``sharding`` it runs
    per-shard under ``shard_map`` with the O(1) hyperprior replicated.
    The PRNG key leaf is never touched.
    """
    k = jnp.asarray(fleet.ng.mu0).shape
    if weight is None:
        w = shrinkage_weight(fleet, strength)
    else:
        w = jnp.broadcast_to(jnp.asarray(weight, jnp.float32), k)
    if sharding is None or len(k) == 0:
        return _shrink_body(fleet, hyper, w)
    return shard_fleet_call(
        lambda fl, ww: _shrink_body(fl, hyper, ww), sharding, (fleet, w)
    )


# --------------------------------------------------------------------------
# surprise
# --------------------------------------------------------------------------
def _hyper_logpdf(
    hyper: Hyperprior, mu: Array, lam: Array, alpha: Array, beta: Array
) -> Array:
    """Log-density of worker parameters under the pooled hyperprior."""
    h = hyper.ng
    scale_mu = 1.0 / jnp.sqrt(jnp.maximum(h.kappa0 * lam, TINY))
    return (
        normal_logpdf(mu, h.mu0, scale_mu)
        + gamma_logpdf(lam, h.nu0, h.psi0)
        + beta_logpdf(alpha, hyper.alpha_prior.a, hyper.alpha_prior.b)
        + beta_logpdf(beta, hyper.beta_prior.a, hyper.beta_prior.b)
    )


def _surprise_body(fleet: GibbsState, hyper: Hyperprior) -> Array:
    lam_k = fleet.ng.nu0 / jnp.maximum(fleet.ng.psi0, TINY)
    a_k, _ = _beta_mean_var(fleet.alpha_prior)
    b_k, _ = _beta_mean_var(fleet.beta_prior)
    logp_k = _hyper_logpdf(hyper, fleet.ng.mu0, lam_k, a_k, b_k)

    # The reference point: the hyperprior's own typical parameters.
    lam_t = hyper.ng.nu0 / jnp.maximum(hyper.ng.psi0, TINY)
    a_t, _ = _beta_mean_var(hyper.alpha_prior)
    b_t, _ = _beta_mean_var(hyper.beta_prior)
    logp_t = _hyper_logpdf(hyper, hyper.ng.mu0, lam_t, a_t, b_t)
    return (logp_t - logp_k).astype(jnp.float32)


@jax.jit
def _surprise_jit(fleet: GibbsState, hyper: Hyperprior) -> Array:
    return _surprise_body(fleet, hyper)


def surprise(
    fleet: GibbsState,
    hyper: Hyperprior,
    *,
    sharding: Optional[ShardingConfig] = None,
) -> Array:
    """Per-worker drift score against the pooled prior; (K,) device-resident.

    The log marginal-likelihood ratio ``log p(theta_typical | hyper) -
    log p(theta_k | hyper)`` where ``theta_k`` are worker k's posterior
    point estimates (Normal-Gamma means for ``(mu, lambda)``, Beta means
    for the exponents) and ``theta_typical`` are the hyperprior's own
    means: ~0 for a worker the fleet prior explains well, large and
    growing as the posterior escapes the pooled prior.  Unlike the raw
    max-over-workers KL drift, the per-worker null distribution does not
    depend on K, so one online-calibrated gate handles any fleet size
    (``repro.serve.gate``).

    Strictly per-worker; with ``sharding`` it runs per-shard under
    ``shard_map`` with only the O(1) hyperprior replicated.
    """
    if sharding is None or jnp.asarray(fleet.ng.mu0).ndim == 0:
        return _surprise_jit(fleet, hyper)
    return shard_fleet_call(
        lambda fl: _surprise_body(fl, hyper), sharding, (fleet,)
    )


# --------------------------------------------------------------------------
# cold-start admission
# --------------------------------------------------------------------------
def init_from_hyperprior(key: Array, count: int, hyper: Hyperprior) -> GibbsState:
    """Fresh per-worker states born from the fleet prior (not the global one).

    The cold-start path of ``sched.add_workers(hierarchical=True)``: the
    newcomers' Normal-Gamma and exponent priors ARE the pooled fleet
    hyperparameters, and their initial chain draws come from those
    distributions — so their very first ``propose`` already reflects what
    the fleet knows, instead of a vague guess the first N observations
    must correct.
    """
    from repro.core import gibbs

    keys = jax.random.split(key, count)
    return jax.vmap(
        lambda k: gibbs.init_state(
            k,
            ng=hyper.ng,
            alpha_prior=hyper.alpha_prior,
            beta_prior=hyper.beta_prior,
        )
    )(keys)
