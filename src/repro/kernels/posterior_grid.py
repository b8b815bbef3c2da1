"""Pallas TPU kernel for the paper's numerical-integration hot spot.

Evaluates the unnormalized log-posteriors of BOTH scaling exponents (alpha,
Eq 10, and beta, Eq 11) on a G-point grid against N telemetry observations,
for a whole fleet of K workers, in ONE kernel launch:

    logp_a[k, g] = -lam_k/2 * sum_n m_kn * ((t_kn - f_kn^g mu_k) f_kn^-beta_k)^2 + prior(g)
    logp_b[k, g] = -lam_k/2 * sum_n m_kn * ((t_kn - f_kn^alpha_k mu_k) f_kn^-g)^2
                   - g * sum_n m_kn log f_kn + prior(g)

Cost is O(K*G*N) transcendental-heavy VPU work — the dominant compute of
every Gibbs sweep once telemetry is production-sized.  Both modes share the
single pow table pg = f^g: the alpha mode consumes pg and pg^2, the beta
mode 1/pg^2, so one launch over one pass of t/f/log f serves both.  The
quadratic form is expanded into three masked inner products

    S_a(g) = A0 - 2 mu <pg, m wb^2 t> + mu^2 <pg^2, m wb^2>,   wb = f^-beta
    S_b(g) = <1/pg^2, m r^2>,                                  r = t - f^alpha mu

(the pure-jnp oracle ``repro.core.moments.log_posterior_grid`` uses the
identical formulation, so interpret-mode parity is tight).  Per cell the
kernel pays two ``exp2`` on the transcendental unit, pg = 2^(g log2 f) and
1/pg^2 = 2^(-2 g log2 f), and five multiplies and three adds on the vector
unit, which bound it.

TPU mapping.  A grid step takes a slab of 128 workers, their observations
up to BN and the grid up to BG: pallas grid = (cdiv(K, 128), G_pad / BG,
cdiv(N, BN)), the fleet axis "parallel", the observation axis last and
"arbitrary" (the output blocks stay resident and accumulate over it).
  * Per worker and observation, the slab's (128, BN) blocks of t, f and mask
    give log2 f and the three weights of the inner products (mu and -lam/2
    folded in), built lane-dense and then transposed once in VMEM to
    (BN, 128): observations on sublanes, workers on lanes.  The A0,
    Jacobian and prior terms are written into the (128, BG) output blocks
    directly, workers on sublanes.
  * The slab is then walked 8 workers at a time.  One lane gather per vreg
    spreads the group's 8 columns over the lanes, 16 lanes a worker, and
    each 16-lane run meets 16 grid points (``pattern`` holds the grid in
    that order).  So every (8 observations, 128 lanes) vreg of the pow table
    is 8 workers x 16 grid points, and the inner products reduce over
    sublanes: adds across vregs, then one sublane reduce.
  * The reduced rows of 8 such chunks form an (8 chunks, 8 workers x 16)
    tile; a block transpose (sublane and lane rolls) turns it into the
    (8 workers, 128 grid points) tile the output holds, added into two
    dense (K, G_pad) outputs stacked into (K, 2, G) by the wrapper (XLA
    folds the stack into its consumers).
The last slab may run past K: rows past K compute on whatever the block
holds, never reach a real worker's lanes and are never written back, and
only the groups that hold a real worker are evaluated.  A partial last N
block is masked in the kernel.  t, f and mask are never copied to pad them;
G is padded to a multiple of 128 (``pattern`` and the grid row only).

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
from jax.experimental.pallas import tpu as pltpu

from repro import obs

Array = jax.Array

DEFAULT_BLOCK_G = 512
DEFAULT_BLOCK_N = 128

_PARAMS = 8  # per-worker scalars: mu, lam, alpha, beta and the four priors
_LANES = 128
_SLAB = _LANES  # workers a grid step: one transposed tile puts them on lanes
_GROUP = 8  # workers that share the lanes of a vreg
_RUN = _LANES // _GROUP  # grid points a worker meets in one vreg
_LOG2E = 1.4426950408889634


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _block_transpose(x):
    """Swap the 8 sublanes with the 8 runs of 16 lanes of an (8, 128) tile:
    out[j, 16 i + c] = x[i, 16 j + c]."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    run = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // _RUN
    out = x
    for d in range(1, _GROUP):  # the run that lies d sublanes off the diagonal
        moved = pltpu.roll(pltpu.roll(x, d, 0), _LANES - _RUN * d, 1)
        out = jnp.where(run == (row - d) % _GROUP, moved, out)
    return out


def _fleet_kernel(params_ref, grid_ref, pattern_ref, t_ref, f_ref, mask_ref,
                  out_a_ref, out_b_ref, *, n_workers, n_obs):
    ki, ni = pl.program_id(0), pl.program_id(2)
    slab, bn = t_ref.shape
    g = grid_ref[...]  # (1, BG)
    p = params_ref[...]  # (SLAB, _PARAMS)
    mu, lam, alpha, beta, a_a, a_b, b_a, b_b = (
        p[:, c:c + 1] for c in range(_PARAMS))  # (SLAB, 1) each
    t = t_ref[...]  # (SLAB, BN)
    logf = jnp.log(jnp.maximum(f_ref[...], 1e-6))
    m = mask_ref[...]
    if n_obs % bn:  # the last N block runs past N
        n_idx = ni * bn + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        t, logf, m = (jnp.where(n_idx < n_obs, x, 0.0) for x in (t, logf, m))

    # S_a = A0 - 2 mu <pg, u> + mu^2 <pg^2, wb2> and S_b = <1/pg^2, m r^2>,
    # each times -lam/2: the per-worker factors ride in the weights.
    half = -0.5 * lam
    wb2 = m * jnp.exp(-2.0 * beta * logf)  # m f^{-2 beta}
    u = wb2 * t
    r = t - jnp.exp(alpha * logf) * mu
    gc = jnp.clip(g, 1e-6, 1.0 - 1e-6)
    lg, l1mg = jnp.log(gc), jnp.log1p(-gc)
    first = ni == 0
    base_a = half * jnp.sum(u * t, axis=1, keepdims=True)  # A0 term
    base_b = -jnp.sum(m * logf, axis=1, keepdims=True) * g  # Jacobian term
    prior_a = (a_a - 1.0) * lg + (a_b - 1.0) * l1mg
    prior_b = (b_a - 1.0) * lg + (b_b - 1.0) * l1mg
    out_a_ref[...] = base_a + jnp.where(first, prior_a, out_a_ref[...])
    out_b_ref[...] = base_b + jnp.where(first, prior_b, out_b_ref[...])

    log2f = logf * _LOG2E
    cols = [x.T for x in (log2f, -2.0 * log2f, lam * mu * u,
                          half * mu * mu * wb2, half * m * r * r)]  # (BN, SLAB)
    lane_worker = jax.lax.broadcasted_iota(jnp.int32, (bn, _LANES), 1) // _RUN
    chunk = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 0)
    n_groups = jnp.minimum(slab // _GROUP, pl.cdiv(n_workers - ki * slab, _GROUP))

    def group(q, carry):
        # (BN, 128) vregs of 8 workers x 16 lanes: worker q*8 + l // 16 at lane l
        idx = q * _GROUP + lane_worker
        l1, l2, w1, w2, w3 = (jnp.take_along_axis(x, idx, axis=1) for x in cols)
        rows = pl.ds(pl.multiple_of(q * _GROUP, _GROUP), _GROUP)
        for tile in range(g.shape[1] // _LANES):
            quad_a = quad_b = jnp.zeros((_GROUP, _LANES), jnp.float32)
            for i in range(_GROUP):  # grid points 128 tile + 16 i + (0..15)
                gp = pattern_ref[_GROUP * tile + i:_GROUP * tile + i + 1, :]
                pg = jnp.exp2(l1 * gp)  # f^g
                qa = jnp.sum(pg * (w1 + pg * w2), axis=0, keepdims=True)
                qb = jnp.sum(jnp.exp2(l2 * gp) * w3, axis=0, keepdims=True)
                quad_a = jnp.where(chunk == i, qa, quad_a)
                quad_b = jnp.where(chunk == i, qb, quad_b)
            lanes = pl.ds(tile * _LANES, _LANES)
            out_a_ref[rows, lanes] += _block_transpose(quad_a)
            out_b_ref[rows, lanes] += _block_transpose(quad_b)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)


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

    The tile follows from the shapes: 128 workers a grid step, all of N up
    to ``block_n`` observations and all of G up to ``block_g`` grid points;
    both blocks are multiples of 128 lanes, and any other value raises
    ``ValueError``.  One ``pallas_call`` covers every worker and both
    exponents.  Tracing adds the useful cells K*G*N to the ``repro.obs``
    counter ``kernels.posterior_grid.cells`` and the cells the tile
    evaluates to ``kernels.posterior_grid.padded_cells``.
    """
    if block_g <= 0 or block_g % _LANES or block_n <= 0 or block_n % _LANES:
        raise ValueError(f"block_g={block_g} and block_n={block_n} must be "
                         f"positive multiples of {_LANES}")
    k, n = t.shape
    g_n = grid.shape[0]
    g_pad = _round_up(g_n, _LANES)
    bg = min(block_g, g_pad)
    bn = n if n <= block_n else block_n
    steps = (pl.cdiv(k, _SLAB), pl.cdiv(g_pad, bg), pl.cdiv(n, bn))
    obs.count("kernels.posterior_grid.cells", k * g_n * n)
    obs.count("kernels.posterior_grid.padded_cells",
              _round_up(k, _GROUP) * steps[1] * bg * steps[2] * bn)

    as_k = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), (k,))
    params = jnp.stack(
        [as_k(x) for x in (mu, lam, alpha, beta, alpha_prior_a, alpha_prior_b,
                           beta_prior_a, beta_prior_b)],
        axis=1,
    )  # (K, 8)
    # Interior padding values (0.5) give finite logs; their columns are cut.
    grid_p = jnp.pad(grid.astype(jnp.float32), (0, g_pad - g_n),
                     constant_values=0.5)
    pattern = jnp.tile(grid_p.reshape(-1, _RUN), (1, _GROUP))  # (G_pad/16, 128)
    obs_block = pl.BlockSpec((_SLAB, bn), lambda ki, gi, ni: (ki, ni))
    out_block = pl.BlockSpec((_SLAB, bg), lambda ki, gi, ni: (ki, gi))
    out_shape = jax.ShapeDtypeStruct((k, g_pad), jnp.float32)
    out_a, out_b = pl.pallas_call(
        functools.partial(_fleet_kernel, n_workers=k, n_obs=n),
        grid=steps,
        in_specs=[
            pl.BlockSpec((_SLAB, _PARAMS), lambda ki, gi, ni: (ki, 0)),
            pl.BlockSpec((1, bg), lambda ki, gi, ni: (0, gi)),
            pl.BlockSpec((bg // _RUN, _LANES), lambda ki, gi, ni: (gi, 0)),
            obs_block,  # t
            obs_block,  # f
            obs_block,  # mask
        ],
        out_specs=[out_block, out_block],
        out_shape=[out_shape, out_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        params,
        grid_p[None, :],
        pattern,
        t.astype(jnp.float32),
        f.astype(jnp.float32),
        mask.astype(jnp.float32),
    )
    return jnp.stack([out_a[:, :g_n], out_b[:, :g_n]], axis=1)

