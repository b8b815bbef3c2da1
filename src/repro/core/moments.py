"""Grid-based posteriors for the scaling exponents alpha, beta — Eqs 10-18.

The posteriors of alpha (Eq 10) and beta (Eq 11) are non-conjugate.  Following
the paper we (i) evaluate the unnormalized log-posterior on a grid over (0, 1),
(ii) compute E and Var by numerical integration (Eqs 16-18), and (iii) fit a
Beta distribution by the method of moments (Eqs 12-15).

``log_posterior_grid`` is the single source of truth for the grid evaluation:
a fused pure-jnp oracle that emits BOTH exponent posteriors from one shared
pow table, batched over an optional leading fleet axis.  It is exactly the
formulation the Pallas TPU kernel (``repro.kernels.posterior_grid``)
implements, so kernel/oracle parity is tight.  The single-mode entry points
``log_posterior_alpha_ref`` (Eq 10) and ``log_posterior_beta_ref`` (Eq 11)
are thin slices of it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .distributions import EPS, normalize_log_density, trapezoid_weights

Array = jax.Array

DEFAULT_GRID_SIZE = 512
GRID_LO = 1e-4
GRID_HI = 1.0 - 1e-4


@jax.custom_batching.custom_vmap
def _pin(x: Array) -> Array:
    """Optimization barrier that survives vmap.

    ``lax.optimization_barrier`` has no batching rule; the custom-vmap rule
    recurses, peeling one batch level per transform until the plain barrier
    applies to the fully-batched value.
    """
    return jax.lax.optimization_barrier(x)


@_pin.def_vmap
def _pin_vmap(axis_size, in_batched, x):
    del axis_size
    return _pin(x), in_batched[0]


class BetaParams(NamedTuple):
    """Beta prior/posterior hyperparameters for one exponent."""

    a: Array  # theta (for alpha) / delta (for beta)
    b: Array  # phi   (for alpha) / eta   (for beta)

    @staticmethod
    def default() -> "BetaParams":
        # Weakly informative, mildly favouring the interior of (0, 1).
        return BetaParams(jnp.asarray(2.0, jnp.float32), jnp.asarray(2.0, jnp.float32))


def exponent_grid(size: int = DEFAULT_GRID_SIZE) -> Array:
    return jnp.linspace(GRID_LO, GRID_HI, size, dtype=jnp.float32)


def log_posterior_grid(
    grid: Array,
    t: Array,
    f: Array,
    mu: Array,
    lam: Array,
    alpha: Array,
    beta: Array,
    alpha_prior: BetaParams,
    beta_prior: BetaParams,
    mask: Optional[Array] = None,
    *,
    chunk_g: int = 16,
    symmetric_grid: bool = False,
) -> Array:
    """Fused evaluation of both exponent log-posteriors (Eqs 10 + 11).

    The unified oracle: one pow table pg = f^g serves both modes — the alpha
    posterior (which consumes the held beta) uses pg and pg^2, the beta
    posterior (which consumes the held alpha, plus the -beta*sum(log f)
    Jacobian term of Eq 4) uses 1/pg^2.  The quadratic forms are expanded
    into masked inner products so each mode is three multiply-accumulate
    passes over the pow table; the Pallas fleet kernel implements the
    same formulation block-wise.

    The grid axis is processed in ``chunk_g``-point blocks (``lax.map`` with
    the pow table pinned behind an optimization barrier): the (..., chunk_g,
    N) table stays cache-resident and is computed exactly once — without the
    barrier XLA rematerializes the exp per consumer, which is the legacy
    path's 2x transcendental cost all over again.

    ``symmetric_grid=True`` asserts grid[i] + grid[G-1-i] is constant (true
    for ``exponent_grid``: a linspace is symmetric about its midpoint) and
    exploits f^{-2 g_i} = f^{-2(g_0 + g_{G-1})} * f^{2 g_{G-1-i}}: the beta
    mode then reads the alpha mode's pg^2 table at the mirrored index
    instead of paying a reciprocal pass per cell.  Algebraically identical
    (fp difference ~1e-7 relative); NEVER set it for a non-symmetric grid.

    Shapes: grid (G,); t/f/mask (..., N); mu/lam/alpha/beta and the prior
    leaves (...) -> (..., 2, G) with [..., 0, :] the alpha posterior and
    [..., 1, :] the beta posterior.  The leading axes are the fleet axes.
    """
    f = jnp.maximum(f, 1e-6)
    logf = jnp.log(f)  # (..., N)
    m = jnp.ones_like(logf) if mask is None else mask.astype(logf.dtype)
    mu_b = jnp.asarray(mu, logf.dtype)[..., None]
    lam_b = jnp.asarray(lam, logf.dtype)[..., None]
    alpha_b = jnp.asarray(alpha, logf.dtype)[..., None]
    beta_b = jnp.asarray(beta, logf.dtype)[..., None]

    # O(N) precomputations shared by every grid chunk.
    wb2 = m * jnp.exp(-2.0 * beta_b * logf)  # (..., N) m * f^{-2 beta}
    u = wb2 * t
    a0 = jnp.sum(u * t, axis=-1)  # (...)
    r = t - jnp.exp(alpha_b * logf) * mu_b  # (..., N)
    w = m * r * r
    if symmetric_grid:
        # S_b[i] = <f^{-2 g_i}, w> = <pg^2, w * f^{-2s}>[G-1-i], s = g_0+g_{G-1}
        w = w * jnp.exp(-2.0 * (grid[0] + grid[-1]) * logf)
    sum_logf = jnp.sum(logf * m, axis=-1)  # (...)

    g_n = grid.shape[0]
    cg = min(chunk_g, g_n)
    g_pad = (-g_n) % cg
    # Interior padding values produce finite logs and are sliced off below.
    grid_p = jnp.pad(grid, (0, g_pad), constant_values=0.5)

    def chunk(gc):
        # alpha mode: S_a = A0 - 2 mu <pg, m wb^2 t> + mu^2 <pg^2, m wb^2>
        # beta  mode: S_b = <1/pg^2, m r^2>  (mirrored <pg^2, w> if symmetric)
        pg = _pin(jnp.exp(gc[:, None] * logf[..., None, :]))  # (..., cg, N) = f^g
        pg2 = pg * pg
        s1 = jnp.einsum("...gn,...n->...g", pg, u)
        s2 = jnp.einsum("...gn,...n->...g", pg2, wb2)
        s3 = jnp.einsum("...gn,...n->...g", pg2 if symmetric_grid else 1.0 / pg2, w)
        qa = -0.5 * lam_b * (a0[..., None] - 2.0 * mu_b * s1 + mu_b * mu_b * s2)
        qb = -0.5 * lam_b * s3
        return qa, qb

    qa_c, qb_c = jax.lax.map(chunk, grid_p.reshape(-1, cg))  # (C, ..., cg)
    join = lambda x: jnp.moveaxis(x, 0, -2).reshape(*x.shape[1:-1], -1)[..., :g_n]
    quad_a = join(qa_c)
    quad_b = join(qb_c)
    if symmetric_grid:
        # Mirrored positions are all within the unpadded [0, G) range, so the
        # flip happens after the padding slice.
        quad_b = jnp.flip(quad_b, axis=-1)

    g = jnp.clip(grid, EPS, 1.0 - EPS)
    lg = jnp.log(g)
    l1mg = jnp.log1p(-g)
    pleaf = lambda x: jnp.asarray(x, logf.dtype)[..., None]
    logp_a = quad_a + (pleaf(alpha_prior.a) - 1.0) * lg + (
        pleaf(alpha_prior.b) - 1.0
    ) * l1mg
    logp_b = (
        quad_b
        - grid * sum_logf[..., None]
        + (pleaf(beta_prior.a) - 1.0) * lg
        + (pleaf(beta_prior.b) - 1.0) * l1mg
    )
    return jnp.stack([logp_a, logp_b], axis=-2)


def log_posterior_alpha_ref(
    grid: Array,
    t: Array,
    f: Array,
    mu: Array,
    lam: Array,
    beta: Array,
    prior: BetaParams,
    mask: Optional[Array] = None,
) -> Array:
    """Unnormalized log p(alpha | T, F, mu, lambda, beta) on ``grid`` (Eq 10).

    Thin slice of the unified oracle ``log_posterior_grid``.
    Shapes: grid (G,), t/f (N,) -> (G,).  Leading batch axes broadcast.
    """
    return log_posterior_grid(
        grid, t, f, mu, lam, jnp.asarray(0.5, jnp.float32), beta,
        prior, BetaParams.default(), mask,
    )[..., 0, :]


def log_posterior_beta_ref(
    grid: Array,
    t: Array,
    f: Array,
    mu: Array,
    lam: Array,
    alpha: Array,
    prior: BetaParams,
    mask: Optional[Array] = None,
) -> Array:
    """Unnormalized log p(beta | T, F, mu, lambda, alpha) on ``grid`` (Eq 11).

    Includes the -beta * sum(log f) Jacobian term from Eq 4.  Thin slice of
    the unified oracle ``log_posterior_grid``.
    """
    return log_posterior_grid(
        grid, t, f, mu, lam, alpha, jnp.asarray(0.5, jnp.float32),
        BetaParams.default(), prior, mask,
    )[..., 1, :]


def moments_from_log_density(grid: Array, logp: Array) -> Tuple[Array, Array]:
    """E and Var by numerical integration of a grid log-density (Eqs 16-18)."""
    pdf = normalize_log_density(logp, grid)
    w = trapezoid_weights(grid)
    e1 = jnp.sum(pdf * w * grid, axis=-1)
    e2 = jnp.sum(pdf * w * grid * grid, axis=-1)
    var = jnp.maximum(e2 - e1 * e1, 1e-12)
    return e1, var


def fit_beta_method_of_moments(mean: Array, var: Array) -> BetaParams:
    """Beta(a, b) from (E, Var) — Eqs 12-15.

    Validity requires Var < E(1-E); we clamp into that region (the grid
    integration can land outside it only through numerical error).
    """
    mean = jnp.clip(mean, 1e-4, 1.0 - 1e-4)
    cap = mean * (1.0 - mean)
    var = jnp.clip(var, 1e-10, 0.999 * cap)
    common = cap / var - 1.0
    a = mean * common
    b = (1.0 - mean) * common
    return BetaParams(jnp.maximum(a, 1e-3), jnp.maximum(b, 1e-3))


def update_alpha_beta_params(
    grid: Array,
    t: Array,
    f: Array,
    mu: Array,
    lam: Array,
    alpha: Array,
    beta: Array,
    alpha_prior: BetaParams,
    beta_prior: BetaParams,
    mask: Optional[Array] = None,
    *,
    use_pallas: bool = False,
    symmetric_grid: bool = False,
) -> Tuple[BetaParams, BetaParams]:
    """Posterior Beta approximations for alpha and beta (one Gibbs sub-step).

    Batched: ``t``/``f``/``mask`` may carry a leading fleet axis K (with
    mu/lam/alpha/beta and the prior leaves shaped (K,)), in which case the
    whole fleet is evaluated fused — with ``use_pallas`` that is ONE kernel
    launch covering every worker and both exponents.  ``symmetric_grid``
    may be set when ``grid`` is midpoint-symmetric (``exponent_grid`` is);
    see ``log_posterior_grid``.
    """
    if use_pallas:
        from repro.kernels import ops as _kops

        batched = t.ndim > 1
        if batched:
            logp = _kops.posterior_grid_fleet(
                grid, t, f, mu, lam, alpha, beta, alpha_prior, beta_prior, mask
            )
        else:
            one = lambda x: jnp.reshape(jnp.asarray(x, jnp.float32), (1,))
            logp = _kops.posterior_grid_fleet(
                grid,
                t[None, :],
                f[None, :],
                one(mu),
                one(lam),
                one(alpha),
                one(beta),
                BetaParams(one(alpha_prior.a), one(alpha_prior.b)),
                BetaParams(one(beta_prior.a), one(beta_prior.b)),
                None if mask is None else mask[None, :],
            )[0]
    else:
        logp = log_posterior_grid(
            grid, t, f, mu, lam, alpha, beta, alpha_prior, beta_prior, mask,
            symmetric_grid=symmetric_grid,
        )
    ea, va = moments_from_log_density(grid, logp[..., 0, :])
    eb, vb = moments_from_log_density(grid, logp[..., 1, :])
    return fit_beta_method_of_moments(ea, va), fit_beta_method_of_moments(eb, vb)
