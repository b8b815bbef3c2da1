"""Plain reference of one drain of the estimator: Algorithm 1 of the paper.

One telemetry batch advances each worker's chain by ``n_iters`` Gibbs sweeps
after power-prior forgetting; the last sweep's conditional posteriors become
the next batch's prior.  A sweep, per worker:

  1. (mu, lambda) | alpha, beta: the conjugate Normal-Gamma update (Eqs 6-9),
     then lambda ~ Gamma(nu_n, rate psi_n), mu ~ N(mu_n, 1 / (kappa_n lambda));
  2. alpha | ... and beta | ...: the grid posteriors (``grid.py``), integrated
     to a mean and variance (Eqs 16-18) and fitted by a Beta (Eqs 12-15),
     then alpha ~ Beta, beta ~ Beta.

The random draws are JAX's, from each worker's key, split five ways per sweep
(next key, lambda, mu, alpha, beta): the same draws the service makes, so the
reference and the service differ by rounding alone.  The posterior-grid step
is computed in ``grid_dtype``; the rest in float32.

A state is a dict of (M,) arrays: ``mu0 kappa0 nu0 psi0`` (Normal-Gamma),
``aa ab`` and ``ba bb`` (the two Beta priors), the samples ``mu lam alpha
beta`` and ``key`` (M, 2) uint32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .grid import exponent_grid, log_posteriors

# What ``gap`` compares: the Normal-Gamma posterior, the means of the two Beta
# posteriors, and the chain's samples.  Not the Beta concentrations a + b: a
# grid posterior only a few grid points wide gives its variance, and so a + b,
# to about 1% under rounding alone (PERF.md, the posterior_gap look).
COMPARED = ("mu0", "kappa0", "nu0", "psi0", "alpha_mean", "beta_mean",
            "mu", "lam", "alpha", "beta")
EPS = 1e-6


def _discount(s: dict, rho: float) -> dict:
    """Power-prior forgetting: pseudo-counts scaled by rho, means kept."""
    s = dict(s)
    s["kappa0"] = s["kappa0"] * rho
    s["nu0"] = jnp.maximum(s["nu0"] * rho, 0.51)
    s["psi0"] = s["psi0"] * rho
    for k in ("aa", "ab", "ba", "bb"):
        s[k] = (s[k] - 1.0) * rho + 1.0
    return s


def _normal_gamma(s, t, f, m, alpha, beta):
    """Eqs 6-9 at the held exponents; returns (mu_n, kappa_n, nu_n, psi_n)."""
    logf = jnp.log(jnp.maximum(f, 1e-6))
    a, b = alpha[:, None], beta[:, None]
    s_cross = jnp.sum(m * jnp.exp((a - 2 * b) * logf) * t, axis=-1)
    s_self = jnp.sum(m * jnp.exp(2 * (a - b) * logf), axis=-1)
    ts = t * jnp.exp(-b * logf)
    s_sq = jnp.sum(m * ts * ts, axis=-1)
    kappa = s["kappa0"] + s_self
    mu = (s["mu0"] * s["kappa0"] + s_cross) / kappa
    nu = s["nu0"] + 0.5 * jnp.sum(m, axis=-1)
    psi = s["psi0"] + 0.5 * (-mu * mu * kappa + s["mu0"] ** 2 * s["kappa0"] + s_sq)
    return mu, kappa, nu, jnp.maximum(psi, 1e-8)


def _beta_fit(grid, logp):
    """Trapezoid mean and variance of a grid log density, then a Beta by moments."""
    d = jnp.diff(grid)
    w = jnp.zeros_like(grid).at[:-1].add(0.5 * d).at[1:].add(0.5 * d)
    p = jnp.exp(logp - jnp.max(logp, axis=-1, keepdims=True))
    pdf = p / jnp.maximum(jnp.sum(p * w, axis=-1, keepdims=True), 1e-30)
    e1 = jnp.sum(pdf * w * grid, axis=-1)
    e2 = jnp.sum(pdf * w * grid * grid, axis=-1)
    var = jnp.maximum(e2 - e1 * e1, 1e-12)
    mean = jnp.clip(e1, 1e-4, 1.0 - 1e-4)
    cap = mean * (1.0 - mean)
    var = jnp.clip(var, 1e-10, 0.999 * cap)
    common = cap / var - 1.0
    return jnp.maximum(mean * common, 1e-3), jnp.maximum((1 - mean) * common, 1e-3)


@functools.partial(jax.jit, static_argnames=("n_iters", "grid_size", "rho",
                                             "grid_dtype"))
def drain(s: dict, t, f, m, *, n_iters: int, grid_size: int, rho: float,
          grid_dtype=jnp.float32) -> dict:
    """The state after one batch ``t, f, m`` (each (M, N)) has been absorbed."""
    grid = exponent_grid(grid_size)
    s = _discount(s, rho)
    gamma = jax.vmap(lambda k, a: jax.random.gamma(k, a))
    normal = jax.vmap(lambda k: jax.random.normal(k, ()))
    beta_draw = jax.vmap(lambda k, a, b: jax.random.beta(k, a, b))

    def sweep(c, _):
        ks = jax.vmap(lambda k: jax.random.split(k, 5))(c["key"])
        mu_n, kappa_n, nu_n, psi_n = _normal_gamma(s, t, f, m, c["alpha"], c["beta"])
        lam = gamma(ks[:, 1], jnp.maximum(nu_n, EPS)) / jnp.maximum(psi_n, 1e-30)
        scale = 1.0 / jnp.sqrt(jnp.maximum(kappa_n * lam, 1e-30))
        mu = mu_n + jnp.maximum(scale, 0.0) * normal(ks[:, 2])
        logp = log_posteriors(grid, t, f, m, mu, lam, c["alpha"], c["beta"],
                              (s["aa"], s["ab"]), (s["ba"], s["bb"]),
                              dtype=grid_dtype)
        aa, ab = _beta_fit(grid, logp[:, 0])
        ba, bb = _beta_fit(grid, logp[:, 1])
        draw = lambda k, a, b: jnp.clip(
            beta_draw(k, jnp.maximum(a, EPS), jnp.maximum(b, EPS)), EPS, 1 - EPS)
        alpha = draw(ks[:, 3], aa, ab)
        beta = draw(ks[:, 4], ba, bb)
        new = dict(key=ks[:, 0], mu=mu, lam=lam, alpha=alpha, beta=beta)
        post = dict(mu0=mu_n, kappa0=kappa_n, nu0=nu_n, psi0=psi_n,
                    aa=aa, ab=ab, ba=ba, bb=bb)
        return new, post

    carry = {k: s[k] for k in ("key", "mu", "lam", "alpha", "beta")}
    carry, posts = jax.lax.scan(sweep, carry, None, length=n_iters)
    out = {k: v[-1] for k, v in posts.items()}
    out.update(carry)
    return out


def belief(s: dict) -> dict:
    """Posterior point estimates: the Normal-Gamma mean of mu, sigma from the
    mean precision nu0 / psi0, and the Beta means of the exponents."""
    lam = s["nu0"] / jnp.maximum(s["psi0"], 1e-30)
    return dict(
        mu=s["mu0"],
        sigma=1.0 / jnp.sqrt(jnp.maximum(lam, 1e-30)),
        alpha=s["aa"] / (s["aa"] + s["ab"]),
        beta=s["ba"] / (s["ba"] + s["bb"]),
    )


def _compared(s: dict) -> dict:
    out = dict(s)
    out["alpha_mean"] = s["aa"] / (s["aa"] + s["ab"])
    out["beta_mean"] = s["ba"] / (s["ba"] + s["bb"])
    return out


def gap(got: dict, want: dict) -> jax.Array:
    """(M,) per worker: the largest relative gap over the compared leaves."""
    got, want = _compared(got), _compared(want)
    rel = [jnp.abs(got[k] - want[k]) / jnp.maximum(jnp.abs(want[k]), 1e-12)
           for k in COMPARED]
    return jnp.max(jnp.stack(rel), axis=0)
