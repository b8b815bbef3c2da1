"""The plain references against the program's own arithmetic, at small sizes.

These tie the benchmark's copies to what they were copied from; the benchmark
itself never imports the program's arithmetic.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import gibbs as ref_gibbs
from bench.reference import makespan as ref_ms
from bench.reference.grid import exponent_grid, log_posteriors


def fleet(k=6, n=12, seed=0):
    rng = np.random.default_rng(seed)
    f = rng.uniform(0.05, 0.9, (k, n)).astype(np.float32)
    mu = rng.uniform(1, 10, k).astype(np.float32)
    t = (f**0.9 * mu[:, None] * (1 + 0.1 * rng.standard_normal((k, n)))).astype(np.float32)
    return t, f, mu


def test_grid_matches_the_program_oracle():
    from repro.core.moments import BetaParams, log_posterior_grid

    t, f, mu = fleet()
    k = len(mu)
    m = np.ones_like(t)
    m[:, -3:] = 0
    lam = np.full(k, 50.0, np.float32)
    al, be = np.full(k, 0.8, np.float32), np.full(k, 0.7, np.float32)
    pa = (np.full(k, 3.0, np.float32), np.full(k, 2.0, np.float32))
    pb = (np.full(k, 2.0, np.float32), np.full(k, 4.0, np.float32))
    grid = exponent_grid(64)
    want = log_posterior_grid(grid, t, f, mu, lam, al, be, BetaParams(*pa),
                              BetaParams(*pb), m)
    got = log_posteriors(grid, t, f, m, mu, lam, al, be, pa, pb)
    scale = 1.0 + np.max(np.abs(np.asarray(want)))
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) / scale < 1e-5


def test_drain_follows_the_program_chain():
    """The reference drain and the program's ``gibbs_batch`` draw alike."""
    from repro.core import gibbs

    t, f, mu = fleet(k=16, n=24, seed=1)
    keys = jax.random.split(jax.random.PRNGKey(3), 16)
    st = jax.vmap(lambda kk: gibbs.init_state(kk, mu_guess=1.0))(keys)
    m = jnp.ones_like(t)
    disc = gibbs.discount_state(st, 0.9)
    got, _ = gibbs.gibbs_batch(disc, t, f, m, n_iters=4, grid_size=64,
                               use_pallas=False)
    leaves = lambda g: dict(
        mu0=g.ng.mu0, kappa0=g.ng.kappa0, nu0=g.ng.nu0, psi0=g.ng.psi0,
        aa=g.alpha_prior.a, ab=g.alpha_prior.b, ba=g.beta_prior.a,
        bb=g.beta_prior.b, mu=g.mu, lam=g.lam, alpha=g.alpha, beta=g.beta,
        key=g.key)
    want = ref_gibbs.drain(leaves(st), t, f, m, n_iters=4, grid_size=64, rho=0.9)
    assert float(np.quantile(ref_gibbs.gap(leaves(got), want), 0.9)) < 1e-4
    np.testing.assert_array_equal(np.asarray(got.key), np.asarray(want["key"]))


def test_flat_makespan_matches_the_program_quadrature():
    from repro.core.frontier import UnitParams, mean_var_completion

    rng = np.random.default_rng(2)
    k = 50
    mu = rng.uniform(1, 4, k).astype(np.float32)
    f = rng.dirichlet(np.ones(k)).astype(np.float32)
    p = dict(mu=mu, sigma=0.1 * mu, alpha=np.full(k, 0.9, np.float32),
             beta=np.full(k, 0.8, np.float32))
    want, _ = mean_var_completion(jnp.asarray(f), UnitParams(**{
        k_: jnp.asarray(v) for k_, v in p.items()}), 1024)
    got = ref_ms.expected_makespan(f, p["mu"], p["sigma"], p["alpha"], p["beta"],
                                   num_points=1024)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_equalizing_split_matches_the_program():
    from repro.core.frontier import UnitParams
    from repro.sched.scheduler import _equalizing_fractions

    rng = np.random.default_rng(3)
    mu = rng.uniform(1, 20, 40).astype(np.float32)
    al = rng.uniform(0.5, 1.0, 40).astype(np.float32)
    want = _equalizing_fractions(UnitParams.of(mu, mu, al, al))
    np.testing.assert_allclose(np.asarray(ref_ms.equalizing_split(mu, al)),
                               np.asarray(want), rtol=1e-5)


def test_workflow_makespan_matches_the_program_simulator():
    from repro.core.frontier import UnitParams
    from repro.sim import simulate_moments

    preds = ((), (0,), (1,), (2, 0))
    rng = np.random.default_rng(4)
    mu = rng.uniform(1, 5, (4, 8)).astype(np.float32)
    f = np.full((4, 8), 1 / 8, np.float32)
    sig, a, b = 0.1 * mu, np.full_like(mu, 0.9), np.full_like(mu, 0.8)
    live = np.ones_like(mu)
    got = float(ref_ms.workflow_makespan(jax.random.PRNGKey(0), f, mu, sig, a, b,
                                         live, preds=preds, num_samples=20000))
    want, _ = simulate_moments(jax.random.PRNGKey(1), preds, f,
                               UnitParams.of(mu, sig, a, b), num_samples=20000)
    np.testing.assert_allclose(got, float(want), rtol=5e-3)
