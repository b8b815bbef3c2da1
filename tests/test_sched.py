"""The pure-functional scheduler API: pytree state, pure transitions,
jit/vmap compatibility, and checkpoint round-trips."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sched
from repro.checkpoint.checkpoint import CheckpointManager
from repro.core.frontier import UnitParams


CFG = sched.SchedulerConfig(n_iters=8, grid_size=64, mu_guess=10.0, opt_steps=60)


def _telemetry(rng, state, true_mu, n=16, alpha=0.9):
    k = len(true_mu)
    fr = np.asarray(sched.propose(state, CFG)[0])
    fmat = np.tile(fr[:, None], (1, n))
    tmat = np.stack([
        np.maximum(f[0] ** alpha * m + 0.3 * rng.normal(size=n), 1e-3)
        for f, m in zip(fmat, true_mu)
    ])
    return sched.Telemetry(jnp.asarray(fmat), jnp.asarray(tmat))


def test_state_is_pytree_of_arrays():
    state = sched.init(CFG, 3, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(state)
    assert leaves and all(hasattr(l, "shape") for l in leaves)
    # per-worker leaves carry the K axis
    assert state.ewma_ll.shape == (3,)
    assert state.gibbs.mu.shape == (3,)


@pytest.mark.no_host_sync
def test_jitted_observe_propose_roundtrip(host_staging):
    """observe ∘ propose composes under one jax.jit — and, via the
    ``no_host_sync`` marker, the composed call runs under
    ``jax.transfer_guard("disallow")``: an accidental host sync inside the
    jitted path fails here instead of shipping."""
    with host_staging():  # eager setup mints keys and device telemetry
        state = sched.init(CFG, 2, jax.random.PRNGKey(0))
        telem = _telemetry(np.random.default_rng(0), state, [5.0, 20.0])

    @jax.jit
    def step(state, telem):
        state, ll = sched.observe(state, telem, CFG)
        fracs, stats = sched.propose(state, CFG)
        return state, ll, fracs, stats

    state2, ll, fracs, stats = step(state, telem)
    with host_staging():  # readbacks for assertions
        assert int(state2.step) == 1
        assert ll.shape == (2,) and np.isfinite(np.asarray(ll)).all()
        np.testing.assert_allclose(float(jnp.sum(fracs)), 1.0, atol=1e-5)
        assert float(stats.e_t) > 0


def test_online_learning_rebalances_functional():
    """The ISSUE's acceptance scenario through the pure API: a 4x-faster
    worker ends up with the bulk of the work."""
    rng = np.random.default_rng(0)
    state = sched.init(CFG, 2, jax.random.PRNGKey(0))
    for _ in range(6):
        state, _ = sched.observe(
            state, _telemetry(rng, state, [5.0, 20.0], n=32), CFG
        )
    fracs, _ = sched.propose(state, CFG)
    assert float(fracs[0]) > 0.6


def test_checkpoint_roundtrip_bit_exact(tmp_path):
    rng = np.random.default_rng(1)
    state = sched.init(CFG, 3, jax.random.PRNGKey(7))
    for _ in range(2):
        state, _ = sched.observe(
            state, _telemetry(rng, state, [4.0, 8.0, 16.0]), CFG
        )

    ckpt = CheckpointManager(str(tmp_path), async_write=False)
    ckpt.save(0, state)
    fresh = sched.init(CFG, 3, jax.random.PRNGKey(0))  # structure template
    restored, _ = ckpt.restore(fresh)

    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_legacy_checkpoint_shape_drift_raises(tmp_path):
    """A checkpoint written with the old fleet-global scalar ``ewma_count``
    must fail restore with ValueError (leaf shape drift), so the trainer's
    legacy fallback path — model-only restore, fresh scheduler beliefs —
    triggers instead of a silent wrong-shape restore crashing mid-run at the
    first eviction."""
    state = sched.init(CFG, 3, jax.random.PRNGKey(0))
    legacy = state._replace(ewma_count=jnp.zeros((), jnp.int32))
    ckpt = CheckpointManager(str(tmp_path), async_write=False)
    ckpt.save(0, legacy)
    with pytest.raises(ValueError, match="shape"):
        ckpt.restore(sched.init(CFG, 3, jax.random.PRNGKey(0)))


def test_restored_trajectory_matches_unrestored(tmp_path):
    """observe -> propose after restore reproduces the unrestored run."""
    rng = np.random.default_rng(2)
    state = sched.init(CFG, 2, jax.random.PRNGKey(3))
    state, _ = sched.observe(state, _telemetry(rng, state, [5.0, 20.0]), CFG)

    ckpt = CheckpointManager(str(tmp_path), async_write=False)
    ckpt.save(0, state)
    restored, _ = ckpt.restore(sched.init(CFG, 2, jax.random.PRNGKey(0)))

    telem = _telemetry(rng, state, [5.0, 20.0])
    s1, ll1 = sched.observe(state, telem, CFG)
    s2, ll2 = sched.observe(restored, telem, CFG)
    np.testing.assert_array_equal(np.asarray(ll1), np.asarray(ll2))
    f1, _ = sched.propose(s1, CFG)
    f2, _ = sched.propose(s2, CFG)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


def test_vmap_multi_tenant_fleet():
    """One device program schedules several tenants at once."""
    tenants, k = 3, 2
    keys = jax.random.split(jax.random.PRNGKey(0), tenants)
    states = jax.vmap(lambda key: sched.init(CFG, k, key))(keys)
    assert states.gibbs.mu.shape == (tenants, k)

    rng = np.random.default_rng(0)
    fr = np.full((tenants, k, 8), 0.5, np.float32)
    t = np.abs(rng.normal(5.0, 0.5, (tenants, k, 8))).astype(np.float32)
    states, ll = jax.vmap(
        lambda s, tt, ff: sched.observe(s, sched.Telemetry(ff, tt), CFG)
    )(states, jnp.asarray(t), jnp.asarray(fr))
    assert ll.shape == (tenants, k)

    fracs, stats = jax.vmap(lambda s: sched.propose(s, CFG))(states)
    assert fracs.shape == (tenants, k)
    np.testing.assert_allclose(np.asarray(fracs).sum(axis=-1), 1.0, atol=1e-5)
    assert np.isfinite(np.asarray(stats.e_t)).all()


def test_observe_pallas_matches_reference_path():
    """Acceptance: ``observe`` through the fused Pallas kernel (interpret mode
    on CPU) against the reference path — same PRNG streams, one launch per
    sweep.  The kernel's grid posterior matches the oracle's to <= 1e-4; the
    posteriors after the sweeps agree within float32 accumulation."""
    from repro.core.moments import exponent_grid, log_posterior_grid
    from repro.kernels import ops

    cfg_pal = dataclasses.replace(CFG, use_pallas=True)
    cfg_ref = dataclasses.replace(CFG, use_pallas=False)
    state = sched.init(CFG, 3, jax.random.PRNGKey(11))
    rng = np.random.default_rng(4)
    telem = _telemetry(rng, state, [4.0, 10.0, 25.0], n=24)

    s_pal, ll_pal = sched.observe(state, telem, cfg_pal)
    s_ref, ll_ref = sched.observe(state, telem, cfg_ref)

    # The grid posterior itself, at the chain's post-sweep samples: the kernel
    # against the formulation the reference path evaluates.
    g = s_ref.gibbs
    args = (exponent_grid(CFG.grid_size), telem.times, telem.fracs,
            g.mu, g.lam, g.alpha, g.beta, g.alpha_prior, g.beta_prior)
    got = np.asarray(ops.posterior_grid_fleet(*args), np.float64)
    want = np.asarray(
        log_posterior_grid(*args, symmetric_grid=True), np.float64
    )
    assert np.max(np.abs(got - want)) / (1.0 + np.max(np.abs(want))) <= 1e-4

    # Post-sweep moments.  The two paths sum each grid cell's N observations
    # in a different order (and the reference mirrors the pow table where the
    # kernel takes a reciprocal), so every one of the G cells of every sweep
    # carries its own float32 rounding, and each sweep's samples feed the
    # next.  The worst-case relative error of m dependent float32 roundings
    # is m * u, with m = n_iters * N * G roundings over the chain:
    # 8 * 24 * 64 * 2^-24 = 7.3e-4.
    u = np.finfo(np.float32).eps / 2
    bound = CFG.n_iters * telem.times.shape[1] * CFG.grid_size * u
    mean = lambda p: np.asarray(p.a / (p.a + p.b))
    np.testing.assert_allclose(
        mean(s_pal.gibbs.alpha_prior), mean(s_ref.gibbs.alpha_prior),
        rtol=bound, atol=bound,
    )
    np.testing.assert_allclose(
        mean(s_pal.gibbs.beta_prior), mean(s_ref.gibbs.beta_prior),
        rtol=bound, atol=bound,
    )
    np.testing.assert_allclose(
        np.asarray(s_pal.gibbs.ng.mu0), np.asarray(s_ref.gibbs.ng.mu0),
        rtol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(ll_pal), np.asarray(ll_ref), rtol=1e-3, atol=1e-2
    )


def test_min_fraction_floor_lifts_a_large_fleet_to_uniform():
    """``min_fraction`` is a per-worker floor on every candidate split.  Once
    it exceeds every worker's share (K = 10^4 against the default 5e-3, 50x
    the uniform share), each candidate is lifted to one value and the
    published split is the uniform one, however different the workers."""
    k = 10_000
    mu = jnp.exp(jnp.linspace(0.0, jnp.log(20.0), k))
    params = UnitParams(
        mu=mu, sigma=0.1 * mu,
        alpha=jnp.full((k,), 0.9), beta=jnp.full((k,), 0.8),
    )
    fr, _ = sched.solve_fractions(params, steps=5, num_points=64)
    # K equal float32 values over their float32 sum: uniform to rounding.
    np.testing.assert_allclose(np.asarray(fr), 1.0 / k, rtol=1e-5)


def test_config_use_pallas_auto_resolves():
    """use_pallas=None (auto) resolves by backend and still observes fine."""
    from repro.kernels.ops import use_pallas_default

    assert CFG.use_pallas is None
    assert isinstance(use_pallas_default(), bool)
    state = sched.init(CFG, 2, jax.random.PRNGKey(0))
    telem = _telemetry(np.random.default_rng(1), state, [5.0, 20.0])
    state, ll = sched.observe(state, telem, CFG)
    assert np.isfinite(np.asarray(ll)).all()


def test_anomaly_flags_degraded_worker():
    rng = np.random.default_rng(3)
    state = sched.init(CFG, 4, jax.random.PRNGKey(1))
    for _ in range(3):
        fr = np.full((4, 16), 0.25, np.float32)
        t = np.abs(rng.normal(5.0, 0.3, (4, 16))).astype(np.float32)
        state, _ = sched.observe(
            state, sched.Telemetry(jnp.asarray(fr), jnp.asarray(t)), CFG
        )
    # worker 2 suddenly runs 6x slower than its learned model
    for _ in range(4):
        times = np.abs(rng.normal(5.0, 0.3, 4))
        times[2] *= 6.0
        state, scores = sched.anomaly(
            state,
            sched.Telemetry(jnp.full(4, 0.25), jnp.asarray(times)),
            CFG,
        )
    scores = np.asarray(scores)
    assert scores[2] == scores.max()
    assert bool(np.asarray(sched.flag_stragglers(state.ewma_ll, 2.0))[2])


def test_admitted_worker_ewma_seeds_at_first_score():
    """Regression: freshness is per worker.  A worker admitted AFTER the
    fleet's first anomaly update must have its EWMA initialized at its own
    first score — the old fleet-global ``ewma_count`` blended it with the
    zero placeholder, biasing fresh admits "healthy" and delaying straggler
    detection."""
    state = sched.init(CFG, 3, jax.random.PRNGKey(2))
    rng = np.random.default_rng(5)
    for _ in range(3):
        fr = np.full((3, 8), 1 / 3, np.float32)
        t = np.abs(rng.normal(5.0, 0.3, (3, 8))).astype(np.float32)
        state, _ = sched.observe(
            state, sched.Telemetry(jnp.asarray(fr), jnp.asarray(t)), CFG
        )
    state, _ = sched.anomaly(
        state, sched.Telemetry(jnp.full(3, 1 / 3), jnp.full(3, 5.0)), CFG
    )
    assert np.asarray(state.ewma_count).shape == (3,)

    state = sched.add_workers(state, 1, CFG)
    assert int(state.ewma_count[3]) == 0  # fresh admit

    # the admit runs 10x slower than the incumbent fleet's behaviour
    times = jnp.asarray([5.0, 5.0, 5.0, 50.0])
    state, scores = sched.anomaly(
        state, sched.Telemetry(jnp.full(4, 0.25), times), CFG
    )
    # EWMA == raw first score for the admit (no zero-blend): recompute it
    p = sched.unit_params(state)
    from repro.core.posterior import posterior_predictive_logpdf

    raw = -float(
        posterior_predictive_logpdf(
            times[3], jnp.asarray(0.25), p.mu[3],
            1.0 / jnp.maximum(p.sigma[3] ** 2, 1e-30), p.alpha[3], p.beta[3],
        )
    )
    np.testing.assert_allclose(float(scores[3]), raw, rtol=1e-5)
    # and the straggling admit is flaggable immediately, not EWMA-lagged
    assert bool(np.asarray(sched.flag_stragglers(state.ewma_ll, 2.0))[3])


def test_anomaly_valid_mask_freezes_failed_worker():
    """Invalid telemetry (hard failures) must leave both the EWMA and the
    freshness counter of the failed worker untouched."""
    state = sched.init(CFG, 3, jax.random.PRNGKey(4))
    rng = np.random.default_rng(6)
    for _ in range(2):
        fr = np.full((3, 8), 1 / 3, np.float32)
        t = np.abs(rng.normal(5.0, 0.3, (3, 8))).astype(np.float32)
        state, _ = sched.observe(
            state, sched.Telemetry(jnp.asarray(fr), jnp.asarray(t)), CFG
        )
    state, _ = sched.anomaly(
        state, sched.Telemetry(jnp.full(3, 1 / 3), jnp.full(3, 5.0)), CFG
    )
    before_ewma = np.asarray(state.ewma_ll).copy()
    before_count = np.asarray(state.ewma_count).copy()

    times = jnp.asarray([5.0, np.inf, 5.0])
    valid = jnp.asarray([True, False, True])
    state, scores = sched.anomaly(
        state, sched.Telemetry(jnp.full(3, 1 / 3), times), CFG, valid
    )
    assert np.isfinite(np.asarray(scores)).all()
    np.testing.assert_array_equal(float(state.ewma_ll[1]), before_ewma[1])
    assert int(state.ewma_count[1]) == int(before_count[1])
    assert int(state.ewma_count[0]) == int(before_count[0]) + 1

    # a per-worker (K,) mask also applies to a batched (K, N) telemetry
    tb = jnp.stack([jnp.full(4, 5.0), jnp.full(4, jnp.inf), jnp.full(4, 5.0)])
    frozen = float(state.ewma_ll[1])
    state, scores = sched.anomaly(
        state, sched.Telemetry(jnp.full((3, 4), 1 / 3), tb), CFG, valid
    )
    assert np.isfinite(np.asarray(scores)).all()
    np.testing.assert_array_equal(float(state.ewma_ll[1]), frozen)


def test_flag_stragglers_valid_mask_excludes_dead_from_baseline():
    """A dead worker's huge stale score must not inflate the median/MAD the
    live fleet is judged against, and the dead worker is never flagged."""
    scores = jnp.asarray([1.0, 1.1, 0.9, 1.05, 500.0, 500.0])
    valid = jnp.asarray([True, True, True, True, False, False])
    flags = np.asarray(sched.flag_stragglers(scores, 3.0, valid))
    assert not flags[4:].any()
    assert not flags[:4].any()
    # two dead workers drag the unmasked median/MAD so far that a genuine
    # live straggler (2.5 vs a ~1.0 pack) escapes; the mask restores detection
    scores2 = jnp.asarray([1.0, 1.1, 0.9, 2.5, 500.0, 500.0])
    assert not np.asarray(sched.flag_stragglers(scores2, 3.0))[3]
    assert np.asarray(sched.flag_stragglers(scores2, 3.0, valid))[3]


def test_elastic_membership_pure():
    state = sched.init(CFG, 4, jax.random.PRNGKey(0))
    state = sched.remove_workers(state, np.array([False, True, False, False]))
    assert sched.num_workers(state) == 3
    assert state.gibbs.mu.shape == (3,)
    state = sched.add_workers(state, 2, CFG)
    assert sched.num_workers(state) == 5
    fracs, _ = sched.propose(state, CFG)
    assert fracs.shape == (5,)
    np.testing.assert_allclose(float(jnp.sum(fracs)), 1.0, atol=1e-5)


def test_objective_plumbing():
    """One Objective value drives the simplex solver consistently."""
    p = UnitParams.of([30.0, 20.0], [2.0, 6.0])
    f_m, st_m = sched.solve_fractions(p, objective=sched.Objective.mean())
    f_r, st_r = sched.solve_fractions(
        p, objective=sched.Objective.mean_var(2.0)
    )
    assert float(st_r.var) <= float(st_m.var) + 1e-6
    assert float(st_r.e_t) >= float(st_m.e_t) - 1e-6

    budget = float(st_m.var) * 0.5
    f_b, st_b = sched.solve_fractions(
        p, objective=sched.Objective.variance_budget(budget)
    )
    assert float(st_b.var) <= budget + 1e-4

    f_d, st_d = sched.solve_fractions(
        p, objective=sched.Objective.deadline_quantile(1.2 * float(st_m.e_t))
    )
    p_meet = -float(st_d.score)
    assert 0.0 <= p_meet <= 1.0 + 1e-6
    np.testing.assert_allclose(float(jnp.sum(f_d)), 1.0, atol=1e-5)


def test_scheduler_shell_delegates():
    """The imperative shell is a view over the pure core."""
    sh = sched.Scheduler(2, config=CFG, seed=0)
    rng = np.random.default_rng(0)
    telem = _telemetry(rng, sh.state, [5.0, 20.0])
    sh.observe(telem)
    assert int(sh.state.step) == 1
    fr, e_t, var = sh.propose_fractions()
    np.testing.assert_allclose(fr.sum(), 1.0, atol=1e-5)
    counts = sh.propose_microbatches(8)
    assert counts.sum() == 8
    # swapping the objective never touches the beliefs
    step_before = int(sh.state.step)
    sh.objective = sched.Objective.mean_var(3.0)
    assert int(sh.state.step) == step_before
