"""The active-set service against the benchmark's plain reference.

A ``ServiceLoop`` with ``active_size=M`` gives M workers a drain a full grid
evaluation (Algorithm 1) and advances the rest through the grid-free
surrogate.  ``bench/reference/active.py`` writes both, and the selection,
plainly; these tests hold the service to it at K = 64, M = 8, G = 32,
N = 16, and pin what the tick reports of the path (``TickInfo.refreshed``,
``.oldest``, the ``serve.tick`` span, ``counters()["grid_refreshed"]``).
"""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import active as ref_active  # noqa: E402
from bench.reference import gibbs as ref_gibbs  # noqa: E402
from repro import obs, sched, serve  # noqa: E402
from repro.serve import service  # noqa: E402

K, M, G, N = 64, 8, 32, 16
N_ITERS, RHO = 4, 0.9
SCHED = sched.SchedulerConfig(n_iters=N_ITERS, grid_size=G, num_points=64,
                              opt_steps=10, discount=RHO)


def _config(active_size=M, **kw):
    return serve.ServeConfig(sched=SCHED, capacity=N, max_staleness=4,
                             active_size=active_size, **kw)


class Telemetry:
    """Seeded rows of a K-worker fleet: t = f^0.9 mu (1 + 0.1 z)."""

    def __init__(self, k=K, seed=0):
        self.rng = np.random.default_rng(seed)
        self.mu = self.rng.uniform(1.0, 10.0, k).astype(np.float32)

    def batch(self):
        k = len(self.mu)
        f = self.rng.uniform(0.05, 0.9, (N, k)).astype(np.float32)
        z = self.rng.standard_normal((N, k)).astype(np.float32)
        return f, (f**0.9 * self.mu * (1.0 + 0.1 * z)).astype(np.float32)

    def beat(self, loop):
        """Push one full ring of rows, tick; returns (info, f, t) with f, t
        as (K, N)."""
        f, t = self.batch()
        for fr, tr in zip(f, t):
            loop.push(fr, tr)
        return loop.tick(), f.T, t.T


def _leaves(g) -> dict:
    return {k: np.asarray(v) for k, v in dict(
        mu0=g.ng.mu0, kappa0=g.ng.kappa0, nu0=g.ng.nu0, psi0=g.ng.psi0,
        aa=g.alpha_prior.a, ab=g.alpha_prior.b, ba=g.beta_prior.a,
        bb=g.beta_prior.b, mu=g.mu, lam=g.lam, alpha=g.alpha, beta=g.beta,
        key=g.key).items()}


def _selection_inputs(loop):
    s = loop.state.sched
    live = np.ones(K, np.float32) if s.live is None else np.asarray(s.live)
    return (np.asarray(loop.state.refresh_age), np.asarray(s.gibbs.ng.nu0),
            np.asarray(s.ewma_ll), live)


# ------------------------------------------------------------------ selection
def test_selection_equals_the_reference_through_two_rotations():
    """Every drain's active set (the workers whose age it set to 0) is the
    reference's top M, from the first drain (all ages tied: lowest indices)
    through two rotations."""
    loop = serve.ServiceLoop(K, config=_config(), seed=0)
    tel = Telemetry(seed=1)
    for _ in range(2 * K // M + 2):
        before = _selection_inputs(loop)
        tel.beat(loop)
        got = np.asarray(loop.state.refresh_age) == 0
        want, _ = ref_active.select(M, *before)
        np.testing.assert_array_equal(got, np.asarray(want))


def test_selection_weighs_youth_anomaly_and_liveness_as_the_reference():
    """With pseudo-counts, anomaly scores and a live mask that differ by
    worker, the service picks the reference's top M, and no dead worker."""
    loop = serve.ServiceLoop(K, config=_config(), seed=0)
    tel = Telemetry(seed=2)
    for _ in range(K // M):
        tel.beat(loop)
    rng = np.random.default_rng(3)
    s = loop.state.sched
    live = np.ones(K, np.float32)
    live[rng.choice(K, 5, replace=False)] = 0.0
    g = s.gibbs._replace(ng=s.gibbs.ng._replace(
        nu0=jnp.asarray(rng.uniform(0.6, 40.0, K), jnp.float32)))
    loop.state = loop.state._replace(sched=s._replace(
        gibbs=g, live=jnp.asarray(live),
        ewma_ll=jnp.asarray(rng.normal(0.0, 2.0, K), jnp.float32)))
    before = _selection_inputs(loop)
    tel.beat(loop)
    got = np.asarray(loop.state.refresh_age) == 0
    want, _ = ref_active.select(M, *before)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert not np.any(got & (live == 0))


# ------------------------------------------------------- one drain, two paths
def test_one_drain_matches_the_reference_of_each_workers_path():
    """After a drain past the first rotation, a selected worker's state is
    Algorithm 1's and every other worker's is the surrogate's.

    Tolerances, in the largest relative gap over the compared leaves
    (``gibbs.gap``).  The surrogate has no grid and the reference repeats
    its float32 arithmetic: it reads 0 here, and 1e-6 leaves room for a few
    ulps.  The grid path integrates a G-point posterior and fits a Beta by
    moments, in another order than the program, and reads up to 2.2e-5
    (median 6e-6) over three seeds; a bfloat16 grid, one precision below
    the float32 the service computes in, reads 1.2e-2 to 2.9e-2 (median
    4.9e-3 to 5.4e-3).  The limits 1e-3 (largest) and 1e-4 (median) lie
    between with room on both sides.  The frozen Beta priors of the
    surrogate and the keys of every worker are exact."""
    loop = serve.ServiceLoop(K, config=_config(), seed=0)
    tel = Telemetry(seed=4)
    for _ in range(K // M + 1):
        tel.beat(loop)
    pre = _leaves(loop.state.sched.gibbs)
    _, f, t = tel.beat(loop)
    post = _leaves(loop.state.sched.gibbs)
    selected = np.asarray(loop.state.refresh_age) == 0
    assert selected.sum() == M
    want = ref_active.drain(pre, t, f, np.ones_like(t), selected,
                            n_iters=N_ITERS, grid_size=G, rho=RHO)
    gap = np.asarray(ref_gibbs.gap(post, want))
    assert gap[~selected].max() < 1e-6
    assert gap[selected].max() < 1e-3 and np.median(gap[selected]) < 1e-4
    for k in ref_active.BETA_KEYS:
        np.testing.assert_array_equal(post[k][~selected], pre[k][~selected])
    np.testing.assert_array_equal(post["key"], np.asarray(want["key"]))


def test_every_live_worker_is_refreshed_within_k_over_m_drains():
    """On a steady fleet, once the first rotation is done, no live worker's
    age passes ceil(K / M) - 1, and ``oldest`` reads the largest age."""
    loop = serve.ServiceLoop(K, config=_config(), seed=0)
    tel = Telemetry(seed=5)
    rotation = math.ceil(K / M)
    for i in range(3 * rotation):
        info, _, _ = tel.beat(loop)
        ages = np.asarray(loop.state.refresh_age)
        assert int(info.oldest) == ages.max()
        if i >= rotation - 1:
            assert ages.max() <= rotation - 1


# ------------------------------------------------------------ what it reports
@pytest.mark.parametrize(
    "active_size,refreshed,oldest",
    [(None, K, 0), (M, M, 1_000_001), (K, K, 0), (2 * K, K, 0)],
    ids=["dense", "active", "active_size_k", "active_size_above_k"],
)
def test_tick_info_on_data_and_empty_ticks(active_size, refreshed, oldest):
    """A data tick refreshes K workers on the dense path (and where
    ``active_size >= K`` takes it), M on the active path; an empty tick
    refreshes none and leaves ``oldest`` as it was."""
    loop = serve.ServiceLoop(K, config=_config(active_size), seed=0)
    info, _, _ = Telemetry(seed=6).beat(loop)
    assert (int(info.refreshed), int(info.oldest)) == (refreshed, oldest)
    empty = loop.tick()
    assert (int(empty.refreshed), int(empty.oldest)) == (0, oldest)
    assert loop.counters()["grid_refreshed"] == refreshed


@pytest.mark.no_host_sync
def test_tick_span_carries_the_path_and_the_tick_syncs_once(host_staging, monkeypatch):
    tel = Telemetry(seed=7)
    with host_staging():  # constructing the loop and pushing stage on the host
        loop = serve.ServiceLoop(K, config=_config(), seed=0)
        for _ in range(2):
            tel.beat(loop)
        f, t = tel.batch()
        for fr, tr in zip(f, t):
            loop.push(fr, tr)
    syncs = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: syncs.append(1) or real(x))
    obs.reset()
    info = loop.tick()  # guarded: no implicit transfer on the tick path
    assert len(syncs) == 1
    with host_staging():
        tick = [s for s in obs.snapshot()["spans"] if s["name"] == "serve.tick"][-1]
        assert tick["attrs"]["refreshed"] == int(info.refreshed) == M
        assert tick["attrs"]["oldest"] == int(info.oldest) == int(
            np.asarray(loop.state.refresh_age).max())
        assert loop.counters()["grid_refreshed"] == 3 * M


def test_active_tick_names_its_stages():
    """The scopes that attribute the active path reach the compiled tick's
    op metadata, where a profile reads them."""
    config = _config()
    state = service.init(config, K, jax.random.PRNGKey(0))
    text = service.tick.lower(state, config).compile().as_text()
    for scope in ("active_select", "active_slab", "surrogate_sweep", "active_merge"):
        assert scope in text, scope
