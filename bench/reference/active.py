"""Plain reference of one active-set drain: the selection, then two kinds of sweep.

A fleet served with an active set of M workers does this on every drain:

  1. Selection.  Every live worker gets a priority

         age + 32 * 16 / (16 + max(2 (nu0 - 1), 0)) + 4 * max(anomaly, 0)

     from its drains since its last full grid evaluation (``age``), its
     Normal-Gamma pseudo-count ``nu0`` and its anomaly score, all read before
     the drain; a dead worker's priority is minus infinity.  The M highest
     are selected, ties going to the lower index.
  2. A selected worker runs Algorithm 1 (``gibbs.drain``): power-prior
     forgetting of all its hyperparameters, then ``n_iters`` sweeps through
     the grid posteriors, and its last sweep's posteriors become its prior.
  3. Every other worker runs the surrogate sweep: the Normal-Gamma block
     discounted and updated exactly as in Algorithm 1, but alpha and beta
     drawn from its Beta priors as they stand, neither discounted nor
     re-chained; no grid is evaluated.  The same five-way key split per
     sweep (next key, lambda, mu, alpha, beta) as in Algorithm 1.

Departures from the program's ``core/compress.py::select_active``: no
``surprise`` term (it enters only with hierarchical pooling, which the
configurations that use this reference leave off); the top M are found by a
stable sort, where the program calls ``lax.top_k``, which breaks ties to the
lower index as well.  Nothing here comes from the program: the arithmetic is
this file's and ``gibbs.py``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import gibbs as ref_gibbs

YOUTH_WEIGHT = 32.0
YOUTH_SCALE = 16.0
ANOMALY_WEIGHT = 4.0
BETA_KEYS = ("aa", "ab", "ba", "bb")


def priority(age, nu, anomaly, live):
    """(K,) the selection priority; minus infinity for a dead worker."""
    ess = jnp.maximum(2.0 * (jnp.asarray(nu, jnp.float32) - 1.0), 0.0)
    pri = (jnp.asarray(age, jnp.float32)
           + YOUTH_WEIGHT * YOUTH_SCALE / (YOUTH_SCALE + ess)
           + ANOMALY_WEIGHT * jnp.maximum(jnp.asarray(anomaly, jnp.float32), 0.0))
    return jnp.where(jnp.asarray(live) > 0, pri, -jnp.inf)


def select(m: int, age, nu, anomaly, live):
    """(selected (K,) bool, priority (K,)): the M workers of highest priority."""
    pri = priority(age, nu, anomaly, live)
    order = jnp.argsort(-pri, stable=True)
    chosen = jnp.zeros(pri.shape, bool).at[order[:m]].set(True)
    return chosen, pri


@functools.partial(jax.jit, static_argnames=("n_iters", "rho"))
def surrogate(s: dict, t, f, m, *, n_iters: int, rho: float) -> dict:
    """The state after a surrogate drain of ``t, f, m`` (each (M, N))."""
    d = ref_gibbs._discount(s, rho)
    for k in BETA_KEYS:  # the Beta priors are frozen: not discounted
        d[k] = s[k]
    gamma = jax.vmap(lambda k, a: jax.random.gamma(k, a))
    normal = jax.vmap(lambda k: jax.random.normal(k, ()))
    beta_draw = jax.vmap(lambda k, a, b: jax.random.beta(k, a, b))
    eps = ref_gibbs.EPS

    def sweep(c, _):
        ks = jax.vmap(lambda k: jax.random.split(k, 5))(c["key"])
        mu_n, kappa_n, nu_n, psi_n = ref_gibbs._normal_gamma(
            d, t, f, m, c["alpha"], c["beta"])
        lam = gamma(ks[:, 1], jnp.maximum(nu_n, eps)) / jnp.maximum(psi_n, 1e-30)
        scale = 1.0 / jnp.sqrt(jnp.maximum(kappa_n * lam, 1e-30))
        mu = mu_n + jnp.maximum(scale, 0.0) * normal(ks[:, 2])
        draw = lambda k, a, b: jnp.clip(
            beta_draw(k, jnp.maximum(a, eps), jnp.maximum(b, eps)), eps, 1 - eps)
        alpha = draw(ks[:, 3], d["aa"], d["ab"])
        beta = draw(ks[:, 4], d["ba"], d["bb"])
        new = dict(key=ks[:, 0], mu=mu, lam=lam, alpha=alpha, beta=beta)
        return new, dict(mu0=mu_n, kappa0=kappa_n, nu0=nu_n, psi0=psi_n)

    with jax.default_matmul_precision("highest"):
        carry = {k: d[k] for k in ("key", "mu", "lam", "alpha", "beta")}
        carry, posts = jax.lax.scan(sweep, carry, None, length=n_iters)
    out = {k: v[-1] for k, v in posts.items()}
    out.update(carry)
    out.update({k: s[k] for k in BETA_KEYS})
    return out


def drain(s: dict, t, f, m, selected, *, n_iters: int, grid_size: int,
          rho: float, grid_dtype=jnp.float32) -> dict:
    """The state after one active-set drain, worker by worker.

    ``s`` holds (W,) leaves of W workers as ``gibbs.py`` names them, ``t, f,
    m`` their (W, N) batch, ``selected`` (W,) bool the path each takes:
    Algorithm 1 where true, the surrogate sweep where false.
    """
    full = ref_gibbs.drain(s, t, f, m, n_iters=n_iters, grid_size=grid_size,
                           rho=rho, grid_dtype=grid_dtype)
    rest = surrogate(s, t, f, m, n_iters=n_iters, rho=rho)
    sel = jnp.asarray(selected)
    pick = lambda a, b: jnp.where(sel.reshape(sel.shape + (1,) * (a.ndim - 1)), a, b)
    return {k: pick(full[k], rest[k]) for k in full}
