"""Hierarchical empirical-Bayes fleet pooling (repro.hier) + calibrated gate.

The load-bearing claims:
  1. the empirical-Bayes refit centers the pooled prior on the fleet;
  2. ``shrink``: weight 0 is a bitwise no-op, a cold worker (ess 0) lands
     exactly on the pool, a mature worker keeps its own data;
  3. cold-start transfer: a hierarchically-admitted worker proposes
     near-fleet-mean in its first cycle and reaches its oracle fraction
     in <= half the observations of a global-prior admit
     (``test_cold_start_transfer_halves_observations`` asserts it);
  4. ``hierarchical=False`` admission is bitwise the legacy global-prior
     path, and the fixed-threshold serve gate never touches the new
     gate/hyperprior state;
  5. ``surprise`` flags the drifted worker, and the calibrated gate's
     skip rate is stable across K = 10^2 and K = 10^4 — where any fixed
     threshold tuned at one K breaks at the other;
  6. sharded shrink/surprise/refit match single-device (same subprocess
     re-run pattern as test_sharding.py on single-device machines).
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import hier, sched, serve
from repro.core import gibbs
from repro.core.sharding import ShardingConfig

multidevice = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs >= 2 devices (see test_sharding)"
)

CFG = sched.SchedulerConfig(
    n_iters=3, grid_size=32, num_points=64, opt_steps=30, mu_guess=1.0
)
# True worker speed far from the global prior (mu_guess=1): a cold admit
# believes it is ~800x faster than the fleet, so the optimizer overloads
# it at birth — the cold-start failure hierarchical pooling removes.
TRUE_MU, TRUE_ALPHA = 800.0, 0.9


def _times(rng, fmat, mu=TRUE_MU):
    return fmat**TRUE_ALPHA * mu * (1.0 + 0.02 * rng.standard_normal(fmat.shape))


def _telemetry(rng, fracs, mu=TRUE_MU, n=16):
    fmat = np.tile(np.asarray(fracs, np.float32)[:, None], (1, n))
    return sched.Telemetry(
        jnp.asarray(fmat, jnp.float32),
        jnp.asarray(_times(rng, fmat, mu), jnp.float32),
    )


def _explore_telemetry(rng, k, mu=TRUE_MU, n=16):
    """Varied per-observation fractions: identifies (mu, alpha) jointly —
    telemetry at one fixed fraction cannot separate them."""
    fmat = rng.uniform(0.05, 0.9, (k, n)).astype(np.float32)
    return sched.Telemetry(
        jnp.asarray(fmat, jnp.float32),
        jnp.asarray(_times(rng, fmat, mu), jnp.float32),
    )


def _clone(scheduler, **overrides):
    """Fork a Scheduler: immutable pytree state is safe to share-then-diverge."""
    s = sched.Scheduler(
        1, config=dataclasses.replace(scheduler.config, **overrides)
    )
    s.state = scheduler.state
    return s


@pytest.fixture(scope="module")
def fleet16():
    """A converged 16-worker fleet of identical mu=8 workers."""
    rng = np.random.default_rng(0)
    s = sched.Scheduler(16, config=CFG, seed=0)
    for _ in range(8):
        s.observe(_explore_telemetry(rng, 16))
    return s


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _tree_close(a, b, tol):
    for la, lb in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_allclose(
            np.asarray(la, np.float64), np.asarray(lb, np.float64),
            atol=tol, rtol=tol,
        )


# ------------------------------------------------------------------- refit
def test_refit_centers_on_fleet(fleet16):
    hyper = fleet16.fit_hyperprior()
    assert float(hyper.n_workers) == 16.0
    # The pool sits inside the fleet's posterior cloud: within the spread
    # of the per-worker means, not at the (far away) global prior.
    mus = np.asarray(fleet16.state.gibbs.ng.mu0)
    assert mus.min() - 1e-3 <= float(hyper.ng.mu0) <= mus.max() + 1e-3
    a_mean = float(
        hyper.alpha_prior.a / (hyper.alpha_prior.a + hyper.alpha_prior.b)
    )
    a_k = np.asarray(fleet16.state.gibbs.alpha_prior.a) / (
        np.asarray(fleet16.state.gibbs.alpha_prior.a)
        + np.asarray(fleet16.state.gibbs.alpha_prior.b)
    )
    assert a_k.min() - 1e-3 <= a_mean <= a_k.max() + 1e-3


# ------------------------------------------------------------------ shrink
def test_shrink_weight_zero_is_bitwise_noop(fleet16):
    hyper = fleet16.fit_hyperprior()
    out = hier.shrink(fleet16.state.gibbs, hyper, weight=0.0)
    assert _leaves_equal(out, fleet16.state.gibbs)


def test_cold_lands_on_pool_mature_keeps_own_data(fleet16):
    hyper = fleet16.fit_hyperprior()
    w = np.asarray(hier.shrinkage_weight(fleet16.state.gibbs))
    assert (w < 0.35).all()  # 8 rounds x 16 obs: the fleet is mature

    cold = jax.tree_util.tree_map(
        lambda x: x[None],
        gibbs.init_state(jax.random.PRNGKey(3), mu_guess=1.0),
    )
    assert float(hier.effective_sample_size(cold)[0]) == 0.0
    assert float(hier.shrinkage_weight(cold)[0]) == 1.0
    warm = hier.shrink(cold, hyper)
    np.testing.assert_allclose(
        float(warm.ng.mu0[0]), float(hyper.ng.mu0), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(warm.ng.kappa0[0]), float(hyper.ng.kappa0), rtol=1e-5
    )

    mature = hier.shrink(fleet16.state.gibbs, hyper)
    own, blended = np.asarray(fleet16.state.gibbs.ng.mu0), np.asarray(
        mature.ng.mu0
    )
    pool = float(hyper.ng.mu0)
    # each mature worker moved strictly less than 35% of the way to the pool
    assert (np.abs(blended - own) <= 0.35 * np.abs(pool - own) + 1e-6).all()


def test_scheduler_shrink_pulls_cold_admit_to_first_cycle_accuracy(fleet16):
    """Satellite: a fresh worker shrunk toward a fast fleet proposes
    near-fleet-mean fractions in its very first propose cycle."""
    s = _clone(fleet16)
    s.add_workers(1, seed=11)  # legacy global-prior admission (mu_guess=1)
    fr_cold, _, _ = s.propose_fractions()
    oracle = 1.0 / 17.0
    assert fr_cold[-1] > 3 * oracle  # cold admit grossly overloaded

    s.shrink()  # ESS-weighted: only the newcomer moves appreciably
    fr_warm, _, _ = s.propose_fractions()
    assert abs(fr_warm[-1] - oracle) < 0.2 * oracle


# ------------------------------------------------- cold-start acceptance
def _obs_to_band(scheduler, oracle, rng, n=4, max_cycles=15):
    """Observations the NEWCOMER needs before its fraction is within 10%
    of oracle; propose happens before each batch, so 0 means 'born ready'."""
    for cycle in range(max_cycles + 1):
        fr, _, _ = scheduler.propose_fractions()
        if abs(fr[-1] - oracle) <= 0.1 * oracle:
            return cycle * n
        scheduler.observe(_telemetry(rng, fr, n=n))
    return (max_cycles + 1) * n


def test_cold_start_transfer_halves_observations(fleet16):
    """ISSUE acceptance: with pooling, a cold worker joining a converged
    K=16 fleet reaches within 10% of its oracle fraction in <= half the
    observations required from the global prior."""
    oracle = 1.0 / 17.0

    pooled = _clone(fleet16, hierarchical=True)
    pooled.add_workers(1, seed=7)
    pooled_obs = _obs_to_band(pooled, oracle, np.random.default_rng(1))

    legacy = _clone(fleet16, hierarchical=False)
    legacy.add_workers(1, seed=7)
    legacy_obs = _obs_to_band(legacy, oracle, np.random.default_rng(1))

    assert pooled_obs <= 15 * 4, "pooled admit never reached the band"
    assert legacy_obs > 0, "global-prior admit was born converged?!"
    assert pooled_obs <= legacy_obs / 2, (pooled_obs, legacy_obs)


def test_add_workers_hierarchical_false_is_bitwise_legacy(fleet16):
    """The default-off path is byte-for-byte the PR 6 admission code."""
    st = fleet16.state
    out = sched.add_workers(st, 2, CFG)

    key, sub = jax.random.split(st.key)
    keys = jax.random.split(sub, 2)
    fresh = jax.vmap(
        lambda k: gibbs.init_state(k, mu_guess=CFG.mu_guess)
    )(keys)
    cat = lambda a, b: jnp.concatenate([jnp.asarray(a), b], axis=0)
    ref = st._replace(
        gibbs=jax.tree_util.tree_map(cat, st.gibbs, fresh),
        ewma_ll=jnp.concatenate([jnp.asarray(st.ewma_ll), jnp.zeros(2)]),
        ewma_count=jnp.concatenate(
            [jnp.asarray(st.ewma_count), jnp.zeros(2, jnp.int32)]
        ),
        key=key,
    )
    assert _leaves_equal(out, ref)


# ---------------------------------------------------------------- surprise
def test_surprise_flags_the_drifted_worker(fleet16):
    hyper = fleet16.fit_hyperprior()
    base = np.asarray(hier.surprise(fleet16.state.gibbs, hyper))
    assert base.shape == (16,)

    g = fleet16.state.gibbs
    mu0 = np.asarray(g.ng.mu0).copy()
    mu0[3] *= 4.0  # worker 3 silently became 4x slower
    drifted = g._replace(ng=g.ng._replace(mu0=jnp.asarray(mu0)))
    scores = np.asarray(hier.surprise(drifted, hyper))
    assert scores.argmax() == 3
    assert scores[3] > np.delete(scores, 3).max() + 1.0


def test_calibrated_gate_skip_rate_stable_across_fleet_sizes():
    """Satellite: the same gate configuration yields the same (near-zero)
    fire rate on the null at K=10^2 and K=10^4 — while a fixed threshold
    tuned at K=10^2 fires almost always at K=10^4."""
    rates = {}
    for k in (100, 10_000):
        rng = np.random.default_rng(0)
        gate, fires, ticks = serve.gate_init(), 0, 120
        for _ in range(ticks):
            fired, gate = serve.gate_update(gate, rng.standard_normal(k).max())
            fires += int(fired)
        rates[k] = fires / ticks
    assert abs(rates[100] - rates[10_000]) <= 0.05, rates
    assert max(rates.values()) <= 0.1, rates

    rng = np.random.default_rng(1)
    small = np.array([rng.standard_normal(100).max() for _ in range(120)])
    fixed_thr = np.quantile(small, 0.95)  # "tuned" on the small fleet
    big = np.array([rng.standard_normal(10_000).max() for _ in range(120)])
    assert (big > fixed_thr).mean() > 0.5  # the fixed gate melts down


def test_gate_warmup_and_no_absorb_on_fire():
    gate = serve.gate_init()
    for stat in (1.0, 1.0, 1.0):  # warmup: calibrate, never fire
        fired, gate = serve.gate_update(gate, stat)
        assert not bool(fired)
    fired, gate = serve.gate_update(gate, 50.0)  # clear regime change
    assert bool(fired)
    assert float(gate.mean) <= 1.0 + 1e-6  # the spike was NOT absorbed
    fired, gate = serve.gate_update(gate, 1.0, update=False)  # masked tick
    assert not bool(fired) and int(gate.count) == 3


# ------------------------------------------------------------- serve wiring
def test_serve_fixed_threshold_never_touches_gate_or_hyper():
    cfg = serve.ServeConfig(
        sched=sched.SchedulerConfig(
            n_iters=2, grid_size=32, num_points=64, opt_steps=10
        ),
        capacity=4, drift_threshold=0.25, max_staleness=4,
    )
    loop = serve.ServiceLoop(2, config=cfg, seed=0)
    rng = np.random.default_rng(0)
    for _ in range(2):
        for _ in range(4):
            f = rng.uniform(0.2, 0.8, 2).astype(np.float32)
            loop.push(f, f**0.9 * np.array([4.0, 8.0], np.float32))
        loop.tick()
    assert int(loop.state.gate.count) == 0  # baseline never calibrated
    assert float(loop.state.hyper.n_workers) == 0.0  # hyper never refit
    assert loop.counters()["proposes"] >= 1


def test_serve_hierarchical_tick_end_to_end():
    """The jitted tick on the hierarchical path: the hyperprior refits on
    cadence, the surprise statistic drives the calibrated gate, and the
    loop still learns a sensible split."""
    cfg = serve.ServeConfig(
        sched=sched.SchedulerConfig(
            n_iters=2, grid_size=32, num_points=64, opt_steps=10,
            hierarchical=True, hyper_refit_every=2,
        ),
        capacity=4, max_staleness=4,
    )
    loop = serve.ServiceLoop(2, config=cfg, seed=0)
    rng = np.random.default_rng(0)
    for _ in range(6):
        for _ in range(4):
            f = rng.uniform(0.2, 0.8, 2).astype(np.float32)
            loop.push(f, f**0.9 * np.array([2.0, 8.0], np.float32))
        info = loop.tick()
    assert float(loop.state.hyper.n_workers) == 2.0  # refit happened
    assert int(loop.state.gate.count) >= 1  # gate is calibrating
    assert np.isfinite(float(info.drift))
    assert loop.counters()["proposes"] >= 1
    fr = loop.fractions()
    assert abs(float(fr.sum()) - 1.0) < 1e-5 and fr[0] > fr[1]


# ------------------------------------------------------------------ sharded
@multidevice
def test_hier_sharded_parity_refit_shrink_surprise():
    """Sharded refit (psum of 13 scalars), shrink and surprise match the
    single-device program; K chosen non-divisible to exercise padding."""
    cfg = ShardingConfig.auto()
    k = cfg.num_shards + 1
    key = jax.random.PRNGKey(0)
    f = jax.random.uniform(key, (k, 48), minval=0.1, maxval=0.9)
    t = f**0.9 * 10.0
    fleet, _ = gibbs.fit_fleet(key, t, f, n_iters=2, grid_size=32)

    h0 = hier.fit_hyperprior(fleet)
    h1 = hier.fit_hyperprior_sharded(fleet, cfg)
    _tree_close(h0, h1, 1e-4)

    s0 = hier.shrink(fleet, h0)
    s1 = hier.shrink(fleet, h0, sharding=cfg)
    assert bool(jnp.all(s0.key == s1.key))  # PRNG leaf untouched
    _tree_close(
        s0._replace(key=s0.key * 0), s1._replace(key=s1.key * 0), 1e-4
    )

    r0 = hier.surprise(fleet, h0)
    r1 = hier.surprise(fleet, h0, sharding=cfg)
    assert r1.shape == (k,)
    _tree_close(r0, r1, 1e-4)


@pytest.mark.skipif(
    jax.device_count() >= 2, reason="parity suite already ran in-process"
)
def test_hier_multidevice_subprocess():
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env.update(
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=str(repo / "src"),
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__,
         "-k", "sharded", "-p", "no:cacheprovider"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "passed" in r.stdout, r.stdout[-3000:]
