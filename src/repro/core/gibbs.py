"""Algorithm 1 — Gibbs sampling of (mu, sigma, alpha, beta).

The sampler follows the paper exactly:

  * per batch of telemetry (T, F): run ``n_iters`` Gibbs sweeps, each sweep
    - recomputing the Normal-Gamma posterior (Eqs 6-9) at the current
      (alpha, beta) and sampling lambda ~ Gamma(nu_N, psi_N),
      mu ~ N(mu_N, (kappa_N lambda)^{-1});
    - refitting the Beta approximations of alpha and beta (Eqs 10-18) at the
      current (mu, lambda) and sampling alpha, beta from them;
  * chaining batches: the posterior hyperparameters become the next batch's
    prior ("the posterior belief ... can become the prior belief for the next
    batch"), which lets the estimator track drifting systems.

Implementation notes (TPU-native):
  * the whole sweep loop is a ``jax.lax.scan`` inside one jitted function;
  * ``gibbs_batch`` is fleet-native: hand it a state whose leaves carry a
    leading worker axis K (as built by ``vmap(init_state)``) plus (K, N)
    telemetry and every sub-step runs batched — the O(K*G*N) grid posterior
    is then ONE fused Pallas launch per sweep covering all workers and both
    exponents (``use_pallas=True``), not a vmap of per-worker kernels;
  * single-unit states (scalar leaves) take the same code path with K
    collapsed, so ``vmap(gibbs_batch)`` remains valid for exotic batching;
  * ``fit`` streams telemetry batches through ``lax.scan`` with the final
    partial batch padded + masked, so no observation is ever dropped;
  * the fleet axis (including the folded S*K stage-fleet axis of a workflow
    DAG) optionally shards across a device mesh via ``shard_map`` — pass a
    ``core.sharding.ShardingConfig`` as ``sharding=``; results are bitwise
    identical to the single-device program (see ``docs/scaling.md``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .distributions import sample_beta, sample_gamma, sample_normal
from .moments import (
    BetaParams,
    exponent_grid,
    update_alpha_beta_params,
)
from .posterior import NormalGammaParams, log_likelihood, update_normal_gamma
from .sharding import ShardingConfig, shard_fleet_call

Array = jax.Array


class GibbsState(NamedTuple):
    """Carry of the Gibbs chain: prior hyperparameters + current samples."""

    ng: NormalGammaParams
    alpha_prior: BetaParams
    beta_prior: BetaParams
    mu: Array
    lam: Array
    alpha: Array
    beta: Array
    key: Array

    @property
    def sigma(self) -> Array:
        return jnp.sqrt(1.0 / jnp.maximum(self.lam, 1e-30))


def init_state(
    key: Array,
    ng: Optional[NormalGammaParams] = None,
    alpha_prior: Optional[BetaParams] = None,
    beta_prior: Optional[BetaParams] = None,
    mu_guess: float = 1.0,
) -> GibbsState:
    """Draw the initial (alpha, beta) from their priors, as in Algorithm 1."""
    ng = ng if ng is not None else NormalGammaParams.default(mu_guess)
    alpha_prior = alpha_prior if alpha_prior is not None else BetaParams.default()
    beta_prior = beta_prior if beta_prior is not None else BetaParams.default()
    k_a, k_b, k_l, k_m, key = jax.random.split(key, 5)
    alpha = sample_beta(k_a, alpha_prior.a, alpha_prior.b)
    beta = sample_beta(k_b, beta_prior.a, beta_prior.b)
    lam = sample_gamma(k_l, ng.nu0, ng.psi0)
    mu = sample_normal(k_m, ng.mu0, 1.0 / jnp.sqrt(jnp.maximum(ng.kappa0 * lam, 1e-30)))
    return GibbsState(ng, alpha_prior, beta_prior, mu, lam, alpha, beta, key)


def _split5(key: Array) -> Tuple[Array, Array, Array, Array, Array]:
    """Five-way PRNG split, batched over an optional leading worker axis.

    Per-worker keys are split exactly as a vmap of ``jax.random.split`` would,
    so the fleet-native sweep reproduces the legacy vmapped chains bitwise.
    """
    if key.ndim == 1:
        k = jax.random.split(key, 5)
        return k[0], k[1], k[2], k[3], k[4]
    ks = jax.vmap(lambda kk: jax.random.split(kk, 5))(key)  # (K, 5, 2)
    return ks[:, 0], ks[:, 1], ks[:, 2], ks[:, 3], ks[:, 4]


def _sample(fn, key: Array, *params: Array) -> Array:
    """Apply a distribution sampler per worker when keys are batched."""
    if key.ndim == 1:
        return fn(key, *params)
    return jax.vmap(fn)(key, *params)


def _advance(
    state: GibbsState,
    t: Array,
    f: Array,
    mask: Optional[Array],
    *,
    n_iters: int,
    grid_size: int,
    use_pallas: bool,
    chain_priors: bool,
) -> Tuple[GibbsState, Array]:
    """One telemetry batch of Gibbs sweeps (the ``gibbs_batch`` body).

    Strictly per-worker: no operation mixes rows of the fleet axis, which is
    what makes the whole function safe to ``shard_map`` over K — each shard
    runs this exact program on its slice of the fleet and the results
    concatenate to the single-device answer bitwise.
    """
    grid = exponent_grid(grid_size)

    def sweep(carry, _):
        st = carry
        key, k_l, k_m, k_a, k_b = _split5(st.key)

        # -- (mu, lambda) block: conjugate update at current (alpha, beta).
        ng_post = update_normal_gamma(st.ng, t, f, st.alpha, st.beta, mask)
        lam = _sample(sample_gamma, k_l, ng_post.nu0, ng_post.psi0)
        mu = _sample(
            sample_normal, k_m, ng_post.mu0,
            1.0 / jnp.sqrt(jnp.maximum(ng_post.kappa0 * lam, 1e-30)),
        )

        # -- (alpha, beta) block: grid posterior -> Beta moment fit -> sample.
        a_post, b_post = update_alpha_beta_params(
            grid, t, f, mu, lam, st.alpha, st.beta,
            st.alpha_prior, st.beta_prior, mask, use_pallas=use_pallas,
            symmetric_grid=True,  # exponent_grid is a symmetric linspace
        )
        alpha = _sample(sample_beta, k_a, a_post.a, a_post.b)
        beta = _sample(sample_beta, k_b, b_post.a, b_post.b)

        new_st = GibbsState(st.ng, st.alpha_prior, st.beta_prior, mu, lam, alpha, beta, key)
        return new_st, (ng_post, a_post, b_post)

    state, (ng_hist, a_hist, b_hist) = jax.lax.scan(
        sweep, state, None, length=n_iters
    )

    last = lambda tree: jax.tree_util.tree_map(lambda x: x[-1], tree)
    ng_post, a_post, b_post = last(ng_hist), last(a_hist), last(b_hist)

    if chain_priors:
        state = state._replace(ng=ng_post, alpha_prior=a_post, beta_prior=b_post)

    ll = log_likelihood(t, f, state.mu, state.lam, state.alpha, state.beta, mask)
    return state, ll


def _advance_surrogate(
    state: GibbsState,
    t: Array,
    f: Array,
    mask: Optional[Array],
    *,
    n_iters: int,
    chain_priors: bool,
) -> Tuple[GibbsState, Array]:
    """Grid-free Gibbs sweeps against the compressed exponent posterior.

    The stored Beta hyperparameters ARE the moment-matched surrogate of the
    exponent posterior (``core.compress``): instead of re-evaluating the
    (2, G) log-posterior grid, each sweep samples (alpha, beta) directly from
    the frozen Beta fit and runs only the conjugate Normal-Gamma block.  The
    Beta priors are never re-chained — they stay frozen until the worker next
    enters the active set and earns a full grid refresh.

    PRNG discipline matches ``_advance`` split-for-split, so a worker keeps a
    coherent key stream while it alternates between the two paths.
    """

    def sweep(carry, _):
        st = carry
        key, k_l, k_m, k_a, k_b = _split5(st.key)

        ng_post = update_normal_gamma(st.ng, t, f, st.alpha, st.beta, mask)
        lam = _sample(sample_gamma, k_l, ng_post.nu0, ng_post.psi0)
        mu = _sample(
            sample_normal, k_m, ng_post.mu0,
            1.0 / jnp.sqrt(jnp.maximum(ng_post.kappa0 * lam, 1e-30)),
        )

        alpha = _sample(sample_beta, k_a, st.alpha_prior.a, st.alpha_prior.b)
        beta = _sample(sample_beta, k_b, st.beta_prior.a, st.beta_prior.b)

        new_st = GibbsState(st.ng, st.alpha_prior, st.beta_prior, mu, lam, alpha, beta, key)
        return new_st, ng_post

    state, ng_hist = jax.lax.scan(sweep, state, None, length=n_iters)
    ng_post = jax.tree_util.tree_map(lambda x: x[-1], ng_hist)

    if chain_priors:
        # Only the conjugate block chains; the Beta surrogate stays frozen.
        state = state._replace(ng=ng_post)

    ll = log_likelihood(t, f, state.mu, state.lam, state.alpha, state.beta, mask)
    return state, ll


def _advance_active(
    state: GibbsState,
    t: Array,
    f: Array,
    mask: Optional[Array],
    active_idx: Array,
    *,
    n_iters: int,
    grid_size: int,
    use_pallas: bool,
    chain_priors: bool,
) -> Tuple[GibbsState, Array]:
    """Active-set advance: full grid for the gathered M-worker slab, the
    compressed surrogate for everyone else, scatter-merged back to (K,).

    Because ``_advance`` is strictly per-worker (no op mixes fleet rows), the
    gathered slab computes exactly what the same rows would compute inside a
    dense launch — with ``active_idx = arange(K)`` the result is bitwise the
    dense path.
    """
    m = jnp.ones_like(t) if mask is None else jnp.broadcast_to(mask, t.shape)

    with jax.named_scope("active_slab"):
        take = lambda x: x[active_idx]
        slab = jax.tree_util.tree_map(take, state)
        slab, ll_slab = _advance(
            slab, take(t), take(f), take(m),
            n_iters=n_iters, grid_size=grid_size, use_pallas=use_pallas,
            chain_priors=chain_priors,
        )

    with jax.named_scope("surrogate_sweep"):
        rest, ll_rest = _advance_surrogate(
            state, t, f, m, n_iters=n_iters, chain_priors=chain_priors
        )

    with jax.named_scope("active_merge"):
        put = lambda full, part: full.at[active_idx].set(part)
        merged = jax.tree_util.tree_map(put, rest, slab)
        return merged, put(ll_rest, ll_slab)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_iters", "grid_size", "use_pallas", "chain_priors", "sharding"
    ),
)
def gibbs_batch(
    state: GibbsState,
    t: Array,
    f: Array,
    mask: Optional[Array] = None,
    *,
    n_iters: int = 20,
    grid_size: int = 512,
    use_pallas: bool = False,
    chain_priors: bool = True,
    sharding: Optional[ShardingConfig] = None,
    active_idx: Optional[Array] = None,
) -> Tuple[GibbsState, Array]:
    """Process one telemetry batch; returns (new_state, log_likelihood).

    Fleet-native: ``state`` may carry a leading worker axis K on every leaf
    (with t/f/mask shaped (K, N)), in which case all K chains advance inside
    one program and the grid posterior is a single fused evaluation — one
    Pallas launch per sweep when ``use_pallas`` — instead of K separate ones.

    With ``sharding`` (a ``core.sharding.ShardingConfig``) the fleet axis is
    additionally partitioned across the mesh's ``workers`` devices via
    ``shard_map``: each shard advances its K/n_shards chains through the same
    fused per-shard program (kernel launch included), the grid stays
    replicated, and only the small per-worker outputs cross shards.  The
    per-worker math never mixes fleet rows, so the sharded result equals the
    single-device result bitwise (PRNG splits are per-worker).  K not
    divisible by the shard count is padded with masked-out dummy workers and
    sliced back.  Single-unit states ((N,) telemetry) ignore ``sharding``.

    Args:
      state: current chain state (prior hyperparameters + samples).
      t, f: observations, shape (N,) or (K, N).
      mask: optional validity mask, same shape as ``t``.
      chain_priors: if True (paper's Algorithm 1), the batch posterior becomes
        the next batch's prior.
      sharding: optional fleet-axis device sharding; None = single device.
      active_idx: optional (M,) int array of fleet rows to advance through the
        full grid path; the remaining K-M workers advance through the grid-free
        compressed surrogate (``core.compress``).  M is static (fixed-size
        active set), values are traced.  Bitwise-equal to the dense path when
        ``active_idx = arange(K)``.  Single-device only (the slab gather is a
        cross-shard op); combine with ``sharding=None``.
    """
    kw = dict(
        n_iters=n_iters,
        grid_size=grid_size,
        use_pallas=use_pallas,
        chain_priors=chain_priors,
    )
    if active_idx is not None and t.ndim >= 2:
        if sharding is not None:
            raise ValueError(
                "active_idx is a single-device path; pass sharding=None"
            )
        return _advance_active(state, t, f, mask, active_idx, **kw)
    if sharding is None or t.ndim < 2:
        return _advance(state, t, f, mask, **kw)

    # Dummy workers added by the pad (when K % n_shards != 0) carry
    # duplicated finite state rows and fully-masked telemetry: they compute
    # a discarded posterior from zero observations and cannot touch real
    # rows (no cross-worker ops).
    m = jnp.ones_like(t) if mask is None else jnp.broadcast_to(mask, t.shape)
    return shard_fleet_call(
        functools.partial(_advance, **kw),
        sharding,
        (state, t, f, m),
        mask_index=3,
    )


def discount_state(state: GibbsState, rho: float) -> GibbsState:
    """Power-prior forgetting (beyond-paper extension, DESIGN.md §8).

    Algorithm 1 chains posterior -> prior with full weight, so a long healthy
    history makes the estimator sluggish when the system drifts.  Scaling the
    pseudo-count hyperparameters by rho in (0, 1] keeps every posterior MEAN
    but widens the distributions — equivalent to exponentially down-weighting
    old evidence.  rho=1 recovers the paper exactly.
    """
    if rho >= 1.0:
        return state
    ng = state.ng
    ng = NormalGammaParams(
        mu0=ng.mu0,
        kappa0=ng.kappa0 * rho,
        nu0=jnp.maximum(ng.nu0 * rho, 0.51),  # keep Gamma proper
        psi0=ng.psi0 * rho,
    )
    soften = lambda p: BetaParams(
        a=(p.a - 1.0) * rho + 1.0, b=(p.b - 1.0) * rho + 1.0
    )
    return state._replace(
        ng=ng,
        alpha_prior=soften(state.alpha_prior),
        beta_prior=soften(state.beta_prior),
    )


def fit(
    key: Array,
    t: Array,
    f: Array,
    *,
    batch_size: int = 32,
    n_iters: int = 20,
    grid_size: int = 512,
    mu_guess: Optional[float] = None,
    use_pallas: bool = False,
) -> Tuple[GibbsState, Array]:
    """Fit one unit's parameters from a telemetry stream (N,) in batches.

    The stream is driven by one ``lax.scan`` (a single compiled program per
    (batch_size, n_iters, grid_size) signature rather than a Python loop of
    dispatches).  The final partial batch is padded and masked, so all N
    observations influence the posterior — the legacy driver silently
    dropped the tail ``n % batch_size`` observations.

    Returns the final state and the per-batch log-likelihood trace
    (the paper's Fig 5 curve).

    >>> import jax, jax.numpy as jnp
    >>> key = jax.random.PRNGKey(0)
    >>> f = jax.random.uniform(key, (48,), minval=0.1, maxval=0.9)
    >>> t = f**0.8 * 10.0                       # noiseless t = f^alpha mu
    >>> state, lls = fit(key, t, f, batch_size=32, n_iters=4, grid_size=64)
    >>> lls.shape                               # ceil(48 / 32) batches
    (2,)
    >>> bool(abs(state.ng.mu0 - 10.0) < 2.0)    # posterior mean near truth
    True
    """
    n = t.shape[-1]
    n_batches = max(-(-n // batch_size), 1)
    n_padded = n_batches * batch_size
    # Padding observations carry mask=0 and interior dummy values: exact
    # no-ops on every masked reduction.
    t_b = jnp.pad(t, (0, n_padded - n)).reshape(n_batches, batch_size)
    f_b = jnp.pad(f, (0, n_padded - n), constant_values=0.5).reshape(
        n_batches, batch_size
    )
    m_b = (jnp.arange(n_padded) < n).astype(jnp.float32).reshape(
        n_batches, batch_size
    )

    # Keep the guess as a traced array (no float() host sync): ``fit`` must
    # compose under jit/vmap, where forcing concretization raises a
    # TracerConversionError.  Mirrors ``fit_fleet``'s array path.
    guess = jnp.mean(t) / jnp.maximum(jnp.mean(f), 1e-6) if mu_guess is None else mu_guess
    state = init_state(key, mu_guess=guess)

    def step(st, xs):
        tb, fb, mb = xs
        st, ll = gibbs_batch(
            st, tb, fb, mb,
            n_iters=n_iters, grid_size=grid_size, use_pallas=use_pallas,
        )
        return st, ll

    state, lls = jax.lax.scan(step, state, (t_b, f_b, m_b))
    return state, lls


def fit_fleet(
    key: Array,
    t: Array,
    f: Array,
    *,
    n_iters: int = 20,
    grid_size: int = 512,
    mu_guess: Optional[Array] = None,
    use_pallas: bool = False,
    sharding: Optional[ShardingConfig] = None,
) -> Tuple[GibbsState, Array]:
    """Fleet estimation: t, f of shape (K, N) -> per-worker states.

    One device program estimates every worker simultaneously through the
    fleet-native ``gibbs_batch`` — with ``use_pallas`` the grid posterior of
    all K workers and both exponents is one kernel launch per sweep.  This is
    the production path for thousands of nodes.  ``sharding`` additionally
    partitions the K axis across a device mesh (see ``gibbs_batch``); the
    per-worker PRNG splits make the sharded chains match single-device
    chains bitwise.
    """
    k = t.shape[0]
    keys = jax.random.split(key, k)
    if mu_guess is None:
        mu_guess = jnp.mean(t, axis=-1) / jnp.maximum(jnp.mean(f, axis=-1), 1e-6)

    def one(key_i, guess_i):
        ng = NormalGammaParams(
            mu0=guess_i.astype(jnp.float32),
            kappa0=jnp.asarray(1e-3, jnp.float32),
            nu0=jnp.asarray(1.0, jnp.float32),
            psi0=jnp.asarray(1.0, jnp.float32),
        )
        return init_state(key_i, ng=ng)

    states = jax.vmap(one)(keys, mu_guess)
    states, ll = gibbs_batch(
        states, t, f, n_iters=n_iters, grid_size=grid_size,
        use_pallas=use_pallas, sharding=sharding,
    )
    return states, ll


def fold_stage_axis(tree):
    """Fold (S, K, ...) pytree leaves into the fleet axis: (S*K, ...).

    The stacked DAG program estimates every stage's fleet in ONE fleet-native
    ``gibbs_batch`` by presenting the stage-stacked fleet as S*K workers —
    stage-major, so stage s worker k lands at flat row s*K + k.
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.reshape(x, (x.shape[0] * x.shape[1],) + x.shape[2:]), tree
    )


def unfold_stage_axis(tree, num_stages: int):
    """Inverse of :func:`fold_stage_axis`: (S*K, ...) leaves -> (S, K, ...)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.reshape(
            x, (num_stages, x.shape[0] // num_stages) + x.shape[1:]
        ),
        tree,
    )


def fit_dag(
    key: Array,
    t: Array,
    f: Array,
    *,
    n_iters: int = 20,
    grid_size: int = 512,
    mu_guess: Optional[Array] = None,
    use_pallas: bool = False,
    sharding: Optional[ShardingConfig] = None,
) -> Tuple[GibbsState, Array]:
    """Stacked stage-fleet estimation: t, f of shape (S, K, N).

    A workflow pipeline of S stages, each partitioned across K workers, is
    estimated as ONE (S, K, N) program: the stage axis is folded into the
    fleet axis so the whole DAG — every stage, every worker, both exponent
    posteriors — advances through a single fleet-native ``gibbs_batch``
    (one fused Pallas launch per sweep with ``use_pallas``), never a Python
    loop over stages.  PRNG keys are split stage-major (stage s worker k
    gets split index s*K + k), so the result bitwise-matches S independent
    ``fit_fleet`` calls handed the corresponding key slices.

    ``sharding`` partitions the FOLDED S*K stage-fleet axis across a device
    mesh — the sharded program pads S*K (not K) up to the shard count, so
    even awkward (S, K) combinations run on any mesh size.

    Returns per-stage-per-worker states with (S, K) leaves and the (S, K)
    log-likelihood.

    >>> import jax, jax.numpy as jnp
    >>> key = jax.random.PRNGKey(0)
    >>> f = jax.random.uniform(key, (2, 3, 32), minval=0.1, maxval=0.9)
    >>> t = f**0.8 * 10.0                       # 2 stages x 3 workers
    >>> states, ll = fit_dag(key, t, f, n_iters=3, grid_size=64)
    >>> ll.shape, states.mu.shape               # (S, K) leaves throughout
    ((2, 3), (2, 3))
    """
    s, k, n = t.shape
    states, ll = fit_fleet(
        key,
        t.reshape(s * k, n),
        f.reshape(s * k, n),
        n_iters=n_iters,
        grid_size=grid_size,
        mu_guess=None if mu_guess is None else jnp.reshape(mu_guess, (s * k,)),
        use_pallas=use_pallas,
        sharding=sharding,
    )
    return unfold_stage_axis(states, s), ll.reshape(s, k)
