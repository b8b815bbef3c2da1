"""Plain reference of the exponent posteriors on a grid (the paper's Eqs 10, 11).

For each worker, with its telemetry ``t, f`` (masked by ``m``), its current
``mu, lam`` and the held other exponent:

    log p(a = g) = -lam/2 sum_n m ((t - f^g mu) f^-beta)^2
                   + (A_a - 1) log g + (A_b - 1) log(1 - g)
    log p(b = g) = -lam/2 sum_n m ((t - f^alpha mu) f^-g)^2
                   - g sum_n m log f + (B_a - 1) log g + (B_b - 1) log(1 - g)

written as the equations read, residual first, with no expansion of the
square.  ``dtype`` is the precision the grid is computed in: float32 is the
reference, bfloat16 its control.  Nothing here comes from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def exponent_grid(size: int) -> jax.Array:
    """The G-point exponent grid on [1e-4, 1 - 1e-4] (a linspace)."""
    return jnp.linspace(1e-4, 1.0 - 1e-4, size, dtype=jnp.float32)


def log_posteriors(grid, t, f, m, mu, lam, alpha, beta, prior_a, prior_b,
                   dtype=jnp.float32):
    """(M, 2, G) unnormalised log posteriors of alpha ([:, 0]) and beta ([:, 1]).

    ``t, f, m`` are (M, N); ``mu, lam, alpha, beta`` (M,); ``prior_a`` and
    ``prior_b`` are the (a, b) pairs of the two Beta priors, each (M,).
    """
    with jax.default_matmul_precision("highest"):
        c = lambda x: jnp.asarray(x, jnp.float32).astype(dtype)
        g = c(grid)[None, :, None]  # (1, G, 1)
        logf = jnp.log(jnp.maximum(c(f), 1e-6))[:, None, :]  # (M, 1, N)
        tt = c(t)[:, None, :]
        mm = c(m)[:, None, :]
        mu_, lam_, al, be = (c(x)[:, None, None] for x in (mu, lam, alpha, beta))
        res_a = (tt - jnp.exp(g * logf) * mu_) * jnp.exp(-be * logf)
        res_b = (tt - jnp.exp(al * logf) * mu_) * jnp.exp(-g * logf)
        s_a = jnp.sum(mm * res_a * res_a, axis=-1)  # (M, G)
        s_b = jnp.sum(mm * res_b * res_b, axis=-1)
        sum_logf = jnp.sum(mm * logf, axis=-1)  # (M, 1)
        gg = c(grid)[None, :]
        lg = jnp.log(jnp.clip(gg, 1e-6, 1.0 - 1e-6))
        l1g = jnp.log1p(-jnp.clip(gg, 1e-6, 1.0 - 1e-6))
        lam2 = lam_[:, :, 0]
        pa = (c(prior_a[0])[:, None] - 1) * lg + (c(prior_a[1])[:, None] - 1) * l1g
        pb = (c(prior_b[0])[:, None] - 1) * lg + (c(prior_b[1])[:, None] - 1) * l1g
        out_a = -0.5 * lam2 * s_a + pa
        out_b = -0.5 * lam2 * s_b - gg * sum_logf + pb
        return jnp.stack([out_a, out_b], axis=1).astype(jnp.float32)
