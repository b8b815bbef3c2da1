"""Plain references of what a split costs, and the splits a solve must beat.

Flat fleet (the paper's Section 1): worker k takes t_k ~ N(f_k^alpha mu_k,
(f_k^beta sigma_k)^2); the step ends with the slowest worker, so

    E[T] = int_0^inf [1 - prod_k P(t_k <= e)] de,

by the trapezoid rule on Q points over [0, max_k(mean_k + 8 std_k)].

Staged workflow: a Monte-Carlo simulator of the same model.  A stage's time
is the max over its workers of one draw each; a stage starts when all of its
predecessors have finished; the workflow ends with its last stage.  One fixed
set of standard-normal draws prices every split (common random numbers).

Candidate splits: the uniform split, and the makespan-equalising split
(tau with sum_k (tau / mu_k)^(1 / alpha_k) = 1, by bisection in log tau),
each floored at ``min_fraction`` and renormalised.  A published split that
minimises E[T] can be no worse than either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtr


def _mean_std(f, mu, sigma, alpha, beta):
    f = jnp.maximum(f, 1e-9)
    return f**alpha * mu, jnp.maximum(f**beta * sigma, 1e-9)


@functools.partial(jax.jit, static_argnames=("num_points",))
def expected_makespan(f, mu, sigma, alpha, beta, *, num_points: int):
    """E[T] of a flat fleet's step under split ``f``; all arguments (..., K)."""
    mean, std = _mean_std(f, mu, sigma, alpha, beta)
    upper = jnp.maximum(jnp.max(mean + 8.0 * std, axis=-1), 1e-6)  # (...)
    eps = jnp.linspace(0.0, 1.0, num_points)[..., :] * upper[..., None]  # (..., Q)
    cdf = ndtr((eps[..., :, None] - mean[..., None, :]) / std[..., None, :])
    surv = 1.0 - jnp.prod(cdf, axis=-1)  # (..., Q)
    return jnp.trapezoid(surv, eps, axis=-1)


@jax.jit
def equalizing_split(mu, alpha):
    """The split that gives every worker the same expected time, (..., K)."""
    log_mu = jnp.log(jnp.maximum(mu, 1e-6))
    a = jnp.clip(alpha, 0.05, 1.0)

    def total(log_tau):
        return jnp.sum(jnp.exp(jnp.clip((log_tau[..., None] - log_mu) / a,
                                        -60.0, 0.0)), axis=-1)

    hi = jnp.max(log_mu, axis=-1)
    lo = hi - 60.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        big = total(mid) > 1.0
        lo, hi = jnp.where(big, lo, mid), jnp.where(big, mid, hi)
    log_tau = 0.5 * (lo + hi)
    f = jnp.exp(jnp.clip((log_tau[..., None] - log_mu) / a, -60.0, 0.0))
    return f / jnp.sum(f, axis=-1, keepdims=True)


def floored(f, min_fraction: float):
    f = jnp.maximum(f, min_fraction)
    return f / jnp.sum(f, axis=-1, keepdims=True)


def candidates(belief: dict, min_fraction: float):
    """(uniform, equalising), both floored, each shaped like ``belief['mu']``."""
    mu = belief["mu"]
    uni = jnp.full(mu.shape, 1.0 / mu.shape[-1], jnp.float32)
    return (floored(uni, min_fraction),
            floored(equalizing_split(mu, belief["alpha"]), min_fraction))


@functools.partial(jax.jit, static_argnames=("preds", "num_samples"))
def workflow_makespan(key, f, mu, sigma, alpha, beta, live, *, preds, num_samples):
    """Monte-Carlo E[T] of a staged workflow; ``f``, the parameters and the
    {0, 1} ``live`` mask of each stage's workers (..., S, K).  A stage takes
    the longest time among its live workers.

    The same ``key`` gives the same draws for every split it prices.
    """
    mean, std = _mean_std(f, mu, sigma, alpha, beta)
    z = jax.random.normal(key, (num_samples,) + mean.shape[-2:])  # (n, S, K)

    def one(mean, std, live):
        dur = jnp.max(jnp.where(live[None] > 0, mean[None] + std[None] * z, 0.0),
                      axis=-1)  # (n, S)
        fin = []
        for i, ps in enumerate(preds):
            start = jnp.zeros((num_samples,))
            for q in ps:
                start = jnp.maximum(start, fin[q])
            fin.append(start + dur[:, i])
        ends = {q for ps in preds for q in ps}
        last = [fin[i] for i in range(len(preds)) if i not in ends]
        return jnp.mean(functools.reduce(jnp.maximum, last))

    lead = mean.shape[:-2]
    flat = lambda x: x.reshape((-1,) + x.shape[-2:])
    out = jax.lax.map(lambda a: one(*a), (flat(mean), flat(std), flat(live)))
    return out.reshape(lead)
