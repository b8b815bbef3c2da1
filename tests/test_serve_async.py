"""Async propose path + compressed active-set serving: publish-on-completion
semantics, dispatch suppression, sync-path equivalence, and the
``hierarchical=False`` bitwise-legacy guarantee."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, sched, serve

SCHED = sched.SchedulerConfig(n_iters=2, grid_size=32, num_points=64,
                              opt_steps=10)


def _config(**kw):
    base = dict(sched=SCHED, capacity=16, drift_threshold=0.05,
                max_staleness=4)
    base.update(kw)
    return serve.ServeConfig(**base)


def _feed(loop, rounds=2, rows=8, k=3, seed=1):
    """Push ``rows`` telemetry rows then tick, ``rounds`` times."""
    rng = jax.random.PRNGKey(seed)
    mu = jnp.linspace(5.0, 20.0, k)
    infos = []
    for r in range(rounds):
        for i in range(rows):
            kk = jax.random.fold_in(rng, r * rows + i)
            f = jax.random.uniform(kk, (k,), minval=0.1, maxval=0.9)
            loop.push(f, f**0.9 * mu)
        infos.append(loop.tick())
    return infos


class _NeverReady:
    """Stands in for an in-flight device array the solve has not finished."""

    def is_ready(self):
        return False


# -----------------------------------------------------------------------
# async propose: publish-on-completion
# -----------------------------------------------------------------------
def test_async_tick_does_not_publish_until_poll():
    loop = serve.ServiceLoop(3, config=_config(async_propose=True), seed=0)
    infos = _feed(loop, rounds=1)
    assert bool(infos[0].proposed)
    # the solve was dispatched off the tick path but NOT published yet:
    # readers still see the placeholder split at version 0
    assert loop._pending is not None
    assert loop.version == 0
    np.testing.assert_allclose(loop.fractions(), 1 / 3)

    jax.block_until_ready(loop._pending[0])
    assert loop.poll() is True
    assert loop.version == 1
    fr = loop.fractions()
    assert abs(float(fr.sum()) - 1.0) < 1e-5
    assert np.all(fr > 0)
    assert np.isfinite(float(loop.state.stats.e_t))
    # drained once more with nothing new: no spurious publish
    assert loop.poll() is False


def test_async_publication_shares_the_dispatching_tick_beat():
    loop = serve.ServiceLoop(3, config=_config(async_propose=True), seed=0)
    obs.reset()
    _feed(loop, rounds=1)
    jax.block_until_ready(loop._pending[0])
    assert loop.poll() is True
    spans = obs.snapshot()["spans"]
    named = lambda n: [s for s in spans if s["name"] == n]
    (tick,), (dispatch,), (poll,), (pub,) = map(
        named, ("serve.tick", "serve.dispatch_solve", "serve.poll",
                "serve.publish"))
    assert dispatch["parent"] == tick["id"] and pub["parent"] == poll["id"]
    # the publication follows the rows it absorbed: the dispatching beat
    assert tick["beat"] == dispatch["beat"] == pub["beat"] == 0
    assert poll["beat"] == 1
    c = loop.counters()
    assert c["proposes_gate"] + c["proposes_stale"] == c["proposes"] == 1
    assert c["rows_drained"] == c["pushes"] == 8


def test_async_pending_solve_suppresses_redispatch():
    loop = serve.ServiceLoop(3, config=_config(async_propose=True), seed=0)
    marker = (_NeverReady(), None)
    loop._pending = marker
    infos = _feed(loop, rounds=1)
    assert bool(infos[0].proposed)  # the gate fired...
    assert loop._pending is marker  # ...but the in-flight solve was kept
    assert loop.version == 0
    loop._pending = None  # drop the stub before the loop is GC'd


def test_async_bookkeeping_matches_sync_decisions():
    """Gate decisions, staleness resets, and counters are identical in the
    two modes — only WHERE the solve runs differs."""
    sync = serve.ServiceLoop(3, config=_config(), seed=0)
    kasync = serve.ServiceLoop(3, config=_config(async_propose=True), seed=0)
    s_infos = _feed(sync, rounds=3)
    a_infos = _feed(kasync, rounds=3)
    for s, a in zip(s_infos, a_infos):
        assert bool(s.proposed) == bool(a.proposed)
        assert int(s.drained) == int(a.drained)
    assert sync.counters()["proposes"] == kasync.counters()["proposes"]
    assert int(jnp.sum(sync.state.staleness)) == int(
        jnp.sum(kasync.state.staleness)
    )
    # and the eventually-published splits agree (same solve, same params)
    while kasync.poll() or kasync._pending is not None:
        if kasync._pending is not None:
            jax.block_until_ready(kasync._pending[0])
    np.testing.assert_allclose(
        kasync.fractions(), sync.fractions(), rtol=1e-5, atol=1e-6
    )


def test_async_with_hierarchical_and_elastic():
    config = _config(
        async_propose=True,
        sched=sched.SchedulerConfig(
            n_iters=2, grid_size=32, num_points=64, opt_steps=10,
            hierarchical=True, hyper_refit_every=2,
        ),
    )
    loop = serve.ServiceLoop(3, config=config, seed=0)
    _feed(loop, rounds=3)
    if loop._pending is not None:
        jax.block_until_ready(loop._pending[0])
        loop.poll()
    assert loop.version >= 1
    assert abs(float(loop.fractions().sum()) - 1.0) < 1e-5


# -----------------------------------------------------------------------
# compressed active set in the serve loop
# -----------------------------------------------------------------------
def test_active_set_tick_refreshes_every_worker_round_robin():
    config = _config(active_size=2)
    loop = serve.ServiceLoop(4, config=config, seed=0)
    assert loop.state.refresh_age is not None
    _feed(loop, rounds=4, k=4)
    ages = np.asarray(loop.state.refresh_age)
    # with M=2 of K=4 refreshed per drain, no worker waits more than ~K/M
    # drains: every age is small and at least M workers are freshly zero
    assert ages.max() <= 3
    assert int((ages == 0).sum()) >= 2
    assert abs(float(loop.fractions().sum()) - 1.0) < 1e-5


def test_active_set_none_is_structurally_legacy():
    loop = serve.ServiceLoop(3, config=_config(), seed=0)
    assert loop.state.refresh_age is None
    # active_size >= K short-circuits to the dense path as well
    full = serve.ServiceLoop(3, config=_config(active_size=3), seed=0)
    _feed(full, rounds=1)
    assert abs(float(full.fractions().sum()) - 1.0) < 1e-5


def test_active_set_with_async_propose_end_to_end():
    config = _config(active_size=2, async_propose=True)
    loop = serve.ServiceLoop(4, config=config, seed=0)
    _feed(loop, rounds=3, k=4)
    if loop._pending is not None:
        jax.block_until_ready(loop._pending[0])
        loop.poll()
    assert loop.version >= 1
    fr = loop.fractions()
    assert abs(float(fr.sum()) - 1.0) < 1e-5 and np.all(fr > 0)


# -----------------------------------------------------------------------
# hierarchical=False stays bitwise-legacy
# -----------------------------------------------------------------------
def test_non_hierarchical_tick_ignores_hyper_knobs_bitwise():
    """Satellite regression: with ``hierarchical=False`` the mid-life
    shrinkage branch must be dead code — changing its cadence/strength
    knobs cannot perturb a single bit of the tick."""
    a_cfg = _config(sched=sched.SchedulerConfig(
        n_iters=2, grid_size=32, num_points=64, opt_steps=10,
        hierarchical=False, hyper_refit_every=1, hyper_strength=0.9,
    ))
    b_cfg = _config(sched=sched.SchedulerConfig(
        n_iters=2, grid_size=32, num_points=64, opt_steps=10,
        hierarchical=False, hyper_refit_every=64, hyper_strength=0.1,
    ))
    a = serve.ServiceLoop(3, config=a_cfg, seed=0)
    b = serve.ServiceLoop(3, config=b_cfg, seed=0)
    _feed(a, rounds=3)
    _feed(b, rounds=3)

    # hyper_age mirrors the configured cadence at init; everything else —
    # posteriors, splits, gate, staleness — must be bitwise identical
    sa = a.state._replace(hyper_age=jnp.zeros((), jnp.int32))
    sb = b.state._replace(hyper_age=jnp.zeros((), jnp.int32))
    eq = jax.tree_util.tree_map(lambda x, y: bool(jnp.array_equal(x, y)), sa, sb)
    flat = jax.tree_util.tree_leaves(eq)
    assert all(flat), eq
    np.testing.assert_array_equal(a.fractions(), b.fractions())
