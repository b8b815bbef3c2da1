"""Pallas TPU kernel for the paper's numerical-integration hot spot.

Evaluates the unnormalized log-posteriors of BOTH scaling exponents (alpha,
Eq 10, and beta, Eq 11) on a G-point grid against N telemetry observations,
for a whole fleet of K workers, in ONE kernel launch:

    logp_a[k, g] = -lam_k/2 * sum_n m_kn * ((t_kn - f_kn^g mu_k) f_kn^-beta_k)^2 + prior(g)
    logp_b[k, g] = -lam_k/2 * sum_n m_kn * ((t_kn - f_kn^alpha_k mu_k) f_kn^-g)^2
                   - g * sum_n m_kn log f_kn + prior(g)

Cost is O(K*G*N) transcendental-heavy VPU work — the dominant compute of
every Gibbs sweep once telemetry is production-sized.  Both modes share the
single expensive pow table pg = exp(g * log f): the alpha mode consumes pg
and pg^2, the beta mode 1/pg^2, so one launch over one pass of t/f/log f
replaces the legacy two-launch (alpha then beta) schedule and halves memory
traffic.  The quadratic form is expanded into three masked inner products

    S_a(g) = A0 - 2 mu <pg, m wb^2 t> + mu^2 <pg^2, m wb^2>,   wb = f^-beta
    S_b(g) = <1/pg^2, m r^2>,                                  r = t - f^alpha mu

so the per-cell op count collapses to one exp + one reciprocal + three
multiply-accumulate passes (the pure-jnp oracle
``repro.core.moments.log_posterior_grid`` uses the identical formulation, so
interpret-mode parity is tight).

TPU mapping:
  * fleet axis      -> leading pallas grid dimension (one program row per
    worker).  Every per-worker array is laid out (K, 1, X) and its blocks
    squeeze the worker axis, (None, 1, X-block), so a block's trailing two
    dims span the whole unit axis and a lane block: the (8, 128) tiling
    rule holds for any K.
  * grid axis       -> lanes (BG = 128-aligned blocks, or all of G when
    G <= block_g); the shared (1, G) grid row is blocked (1, BG)
  * observation axis -> streamed VMEM blocks (BN >= 128), reduced
    sequentially via the revisiting-output accumulation pattern: pallas
    grid = (K, G/BG, N/BN); both output blocks for a given (k, g-tile) stay
    resident in VMEM while the inner n-loop accumulates into them.
  * per-worker scalars (mu, lam, alpha, beta, priors, sum_logf) ride in a
    packed (1, 16) parameter row mapped to every block of worker k.

On the TPU the kernel lowers to Mosaic (``interpret=False``).  Interpret
mode is for the CPU tests only: it emulates the kernel body and accepts
block shapes the TPU compiler refuses, so ``tests/test_tpu_compile.py``
compiles the kernel for a described v5e chip as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BLOCK_G = 128
DEFAULT_BLOCK_N = 512

_PARAM_WIDTH = 16  # lane-padded per-worker scalar row


def _fleet_kernel(params_ref, grid_ref, t_ref, f_ref, mask_ref, out_a_ref, out_b_ref):
    ni = pl.program_id(2)

    mu = params_ref[0, 0]
    lam = params_ref[0, 1]
    alpha = params_ref[0, 2]
    beta = params_ref[0, 3]
    a_a = params_ref[0, 4]
    a_b = params_ref[0, 5]
    b_a = params_ref[0, 6]
    b_b = params_ref[0, 7]
    sum_logf = params_ref[0, 8]

    g = grid_ref[0, :]  # (BG,)
    f = jnp.maximum(f_ref[0, :], 1e-6)  # (BN,)
    logf = jnp.log(f)
    t = t_ref[0, :]
    m = mask_ref[0, :]

    # One pow table serves both exponents: pg = f^g per (grid, obs) cell.
    pg = jnp.exp(g[:, None] * logf[None, :])  # (BG, BN)
    pg2 = pg * pg
    ipg2 = 1.0 / pg2

    # alpha mode, expanded: S_a = A0 - 2 mu <pg, u> + mu^2 <pg^2, v>
    wb2 = m * jnp.exp(-2.0 * beta * logf)  # m * f^{-2 beta}  (BN,)
    u = wb2 * t
    a0 = jnp.sum(u * t)
    quad_a = -0.5 * lam * (
        a0
        - 2.0 * mu * jnp.sum(pg * u[None, :], axis=1)
        + mu * mu * jnp.sum(pg2 * wb2[None, :], axis=1)
    )  # (BG,)

    # beta mode: S_b = <1/pg^2, m r^2>
    r = t - jnp.exp(alpha * logf) * mu  # (BN,)
    quad_b = -0.5 * lam * jnp.sum(ipg2 * (m * r * r)[None, :], axis=1)  # (BG,)

    @pl.when(ni == 0)
    def _init():
        gc = jnp.clip(g, 1e-6, 1.0 - 1e-6)
        lg = jnp.log(gc)
        l1mg = jnp.log1p(-gc)
        out_a_ref[0, :] = (a_a - 1.0) * lg + (a_b - 1.0) * l1mg + quad_a
        out_b_ref[0, :] = (b_a - 1.0) * lg + (b_b - 1.0) * l1mg - g * sum_logf + quad_b

    @pl.when(ni != 0)
    def _acc():
        out_a_ref[0, :] = out_a_ref[0, :] + quad_a
        out_b_ref[0, :] = out_b_ref[0, :] + quad_b


@functools.partial(
    jax.jit,
    static_argnames=("block_g", "block_n", "interpret"),
)
def posterior_grid_fleet_pallas(
    grid: Array,
    t: Array,
    f: Array,
    mask: Array,
    mu: Array,
    lam: Array,
    alpha: Array,
    beta: Array,
    alpha_prior_a: Array,
    alpha_prior_b: Array,
    beta_prior_a: Array,
    beta_prior_b: Array,
    *,
    block_g: int = DEFAULT_BLOCK_G,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> Array:
    """Fused fleet evaluation of both exponent log-posteriors.

    Shapes: grid (G,); t/f/mask (K, N); mu/lam/alpha/beta and the four prior
    leaves (K,).  Returns (K, 2, G) f32 — [:, 0] is the alpha posterior
    (which consumes beta), [:, 1] the beta posterior (which consumes alpha).

    Inputs are padded to block multiples here; padding observations carry
    mask=0 (exact no-op on the reduction), padding grid points are sliced off.
    One ``pallas_call`` covers every worker and both exponents.
    """
    k, n = t.shape
    g_n = grid.shape[0]
    bg = min(block_g, max(8, g_n))
    bn = min(block_n, max(128, n))

    g_pad = (-g_n) % bg
    n_pad = (-n) % bn
    # Pad grid with interior values (0.5): finite logs, sliced off below.
    grid_p = jnp.pad(grid.astype(jnp.float32), (0, g_pad), constant_values=0.5)
    t_p = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, n_pad)))
    f_p = jnp.pad(f.astype(jnp.float32), ((0, 0), (0, n_pad)), constant_values=0.5)
    mask_p = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, n_pad)))

    f_safe = jnp.maximum(f.astype(jnp.float32), 1e-6)
    sum_logf = jnp.sum(jnp.log(f_safe) * mask.astype(jnp.float32), axis=-1)  # (K,)

    as_k = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), (k,))
    params = jnp.stack(
        [
            as_k(mu),
            as_k(lam),
            as_k(alpha),
            as_k(beta),
            as_k(alpha_prior_a),
            as_k(alpha_prior_b),
            as_k(beta_prior_a),
            as_k(beta_prior_b),
            sum_logf,
        ],
        axis=1,
    )  # (K, 9)
    params = jnp.pad(params, ((0, 0), (0, _PARAM_WIDTH - params.shape[1])))

    n_gb = grid_p.shape[0] // bg
    n_nb = t_p.shape[1] // bn

    # (K, 1, X) arrays with the worker axis squeezed from every block (see
    # "TPU mapping" above): the only layout the TPU compiler accepts for all K.
    row = lambda x: x[:, None, :]
    per_worker = lambda width, col: pl.BlockSpec(
        (None, 1, width), lambda ki, gi, ni: (ki, 0, col(gi, ni))
    )
    obs = per_worker(bn, lambda gi, ni: ni)
    cell = per_worker(bg, lambda gi, ni: gi)
    out_shape = jax.ShapeDtypeStruct((k, 1, grid_p.shape[0]), jnp.float32)
    out_a, out_b = pl.pallas_call(
        _fleet_kernel,
        grid=(k, n_gb, n_nb),
        in_specs=[
            per_worker(_PARAM_WIDTH, lambda gi, ni: 0),  # params
            pl.BlockSpec((1, bg), lambda ki, gi, ni: (0, gi)),  # grid
            obs,  # t
            obs,  # f
            obs,  # mask
        ],
        out_specs=[cell, cell],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
    )(
        row(params),
        grid_p[None, :],
        row(t_p),
        row(f_p),
        row(mask_p),
    )
    return jnp.concatenate([out_a[..., :g_n], out_b[..., :g_n]], axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "block_g", "block_n", "interpret"),
)
def posterior_grid_pallas(
    grid: Array,
    t: Array,
    f: Array,
    mask: Array,
    mu: Array,
    lam: Array,
    other_exp: Array,
    prior_a: Array,
    prior_b: Array,
    *,
    mode: str = "alpha",
    block_g: int = DEFAULT_BLOCK_G,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> Array:
    """Single-unit, single-mode evaluation.  Returns (G,) f32.

    Kept as a K=1 slice of the fused fleet kernel: ``other_exp`` is the held
    exponent the requested mode consumes, the unused mode's inputs are
    interior dummies and its output row is discarded.  Note the kernel body
    is opaque to XLA, so the discarded mode IS computed — callers that need
    both exponents should call ``posterior_grid_fleet_pallas`` once instead
    of this entry twice (that is the whole point of the fusion); this slice
    exists for validation and back-compat.
    """
    if mode not in ("alpha", "beta"):
        raise ValueError(mode)
    dummy = jnp.float32(0.5)
    if mode == "alpha":
        alpha, beta = dummy, other_exp
        a_prior = (prior_a, prior_b)
        b_prior = (jnp.float32(2.0), jnp.float32(2.0))
    else:
        alpha, beta = other_exp, dummy
        a_prior = (jnp.float32(2.0), jnp.float32(2.0))
        b_prior = (prior_a, prior_b)
    out = posterior_grid_fleet_pallas(
        grid,
        t[None, :],
        f[None, :],
        mask[None, :],
        mu,
        lam,
        alpha,
        beta,
        a_prior[0],
        a_prior[1],
        b_prior[0],
        b_prior[1],
        block_g=block_g,
        block_n=block_n,
        interpret=interpret,
    )
    return out[0, 0 if mode == "alpha" else 1]
