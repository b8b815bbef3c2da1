"""Hypothesis property tests on the system's invariants."""
import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.frontier import UnitParams, completion_cdf, pareto_mask
from repro.core.moments import fit_beta_method_of_moments
from repro.core.posterior import NormalGammaParams, update_normal_gamma
from repro.sched import quantize_fractions
from repro.train.train_step import cross_entropy

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=25, derandomize=True
)
hypothesis.settings.load_profile("ci")

pos_floats = st.floats(0.1, 100.0, allow_nan=False)
exponents = st.floats(0.05, 1.0, allow_nan=False)


@given(
    n=st.integers(1, 64),
    mu0=st.floats(-10, 10),
    kappa0=st.floats(1e-3, 10),
    alpha=exponents,
    beta=exponents,
    seed=st.integers(0, 1000),
)
def test_normal_gamma_update_invariants(n, mu0, kappa0, alpha, beta, seed):
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.normal(5, 2, n), jnp.float32)
    f = jnp.asarray(rng.uniform(0.05, 1.0, n), jnp.float32)
    prior = NormalGammaParams(
        jnp.float32(mu0), jnp.float32(kappa0), jnp.float32(1.0), jnp.float32(1.0)
    )
    post = update_normal_gamma(prior, t, f, jnp.float32(alpha), jnp.float32(beta))
    # precision-count only grows; nu grows by exactly N/2; psi stays positive
    assert float(post.kappa0) > float(prior.kappa0)
    np.testing.assert_allclose(float(post.nu0), 1.0 + n / 2, rtol=1e-6)
    assert float(post.psi0) > 0
    assert np.isfinite(float(post.mu0))


@given(
    k=st.integers(1, 4),
    g=st.integers(3, 70),
    n=st.integers(2, 90),
    mu=st.floats(0.5, 50.0),
    lam=st.floats(0.05, 2.0),
    alpha=exponents,
    beta=exponents,
    mask_stride=st.integers(0, 4),
    seed=st.integers(0, 100),
)
@settings(max_examples=12, deadline=None)
def test_fused_kernel_oracle_parity_property(k, g, n, mu, lam, alpha, beta, mask_stride, seed):
    """Fused fleet kernel (interpret mode) == unified oracle for arbitrary
    odd/padded shapes, parameters, and masks, including zeroed columns."""
    from repro.core.moments import BetaParams, log_posterior_grid
    from repro.kernels.posterior_grid import posterior_grid_fleet_pallas

    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.uniform(0.05, 0.95, (k, n)), jnp.float32)
    t = jnp.asarray(
        np.asarray(f) ** alpha * mu
        + np.asarray(f) ** beta * rng.normal(0, 1.0, (k, n)),
        jnp.float32,
    )
    mask = np.ones((k, n), np.float32)
    if mask_stride:
        mask[:, ::mask_stride + 1] = 0.0
    mask = jnp.asarray(mask)
    grid = jnp.linspace(1e-4, 1 - 1e-4, g, dtype=jnp.float32)
    ones = jnp.ones((k,), jnp.float32)
    prior = BetaParams(2.0 * ones, 2.0 * ones)
    got = posterior_grid_fleet_pallas(
        grid, t, f, mask, mu * ones, lam * ones, alpha * ones, beta * ones,
        prior.a, prior.b, prior.a, prior.b, interpret=True,
    )
    want = log_posterior_grid(
        grid, t, f, mu * ones, lam * ones, alpha * ones, beta * ones,
        prior, prior, mask,
    )
    scale = 1.0 + float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=5e-5, atol=5e-5 * scale
    )


@given(
    mean=st.floats(0.05, 0.95),
    var_frac=st.floats(0.01, 0.95),
)
def test_beta_fit_valid_and_mean_preserving(mean, var_frac):
    var = var_frac * mean * (1 - mean)
    fit = fit_beta_method_of_moments(jnp.float32(mean), jnp.float32(var))
    a, b = float(fit.a), float(fit.b)
    assert a > 0 and b > 0
    np.testing.assert_allclose(a / (a + b), mean, rtol=5e-3, atol=5e-3)


@given(
    k=st.integers(2, 6),
    total=st.integers(8, 128),
    seed=st.integers(0, 100),
)
def test_quantize_partition_of_unity(k, total, seed):
    if total < k:
        return
    rng = np.random.default_rng(seed)
    fr = rng.dirichlet(np.ones(k))
    counts = quantize_fractions(fr, total)
    assert counts.sum() == total
    assert (counts >= 1).all()
    # counts approximate fractions within 1 unit + rounding of the floor
    assert np.all(np.abs(counts - fr * total) <= k + 1)


@given(
    k=st.integers(1, 4),
    seed=st.integers(0, 50),
)
def test_completion_cdf_monotone_and_bounded(k, seed):
    rng = np.random.default_rng(seed)
    p = UnitParams.of(rng.uniform(5, 50, k), rng.uniform(0.5, 5, k),
                      rng.uniform(0.5, 1, k), rng.uniform(0.5, 1, k))
    fr = jnp.asarray(rng.dirichlet(np.ones(k)), jnp.float32)
    eps = jnp.linspace(0.0, 100.0, 128)
    cdf = np.asarray(completion_cdf(eps, fr, p))
    assert (cdf >= -1e-6).all() and (cdf <= 1 + 1e-6).all()
    assert (np.diff(cdf) >= -1e-5).all()  # monotone non-decreasing


@given(seed=st.integers(0, 200))
def test_pareto_mask_is_exactly_nondominated_set(seed):
    rng = np.random.default_rng(seed)
    mu = jnp.asarray(rng.uniform(0, 10, 32), jnp.float32)
    var = jnp.asarray(rng.uniform(0, 10, 32), jnp.float32)
    mask = np.asarray(pareto_mask(mu, var))
    mu_n, var_n = np.asarray(mu), np.asarray(var)
    for i in range(32):
        dominated = bool(
            np.any(
                (mu_n <= mu_n[i]) & (var_n <= var_n[i])
                & ((mu_n < mu_n[i]) | (var_n < var_n[i]))
            )
        )
        assert mask[i] == (not dominated)


@given(
    b=st.integers(1, 4),
    t=st.integers(1, 8),
    v=st.integers(4, 32),
    seed=st.integers(0, 100),
)
def test_cross_entropy_bounds_and_masking(b, t, v, seed):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(b, t, v)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(b, t)), jnp.int32)
    xent, z = cross_entropy(logits, labels, v)
    assert float(xent) >= -1e-5
    # fully-masked labels give zero loss
    xent_m, _ = cross_entropy(logits, jnp.full((b, t), -100, jnp.int32), v)
    assert abs(float(xent_m)) < 1e-6
    # uniform logits -> log(v)
    xent_u, _ = cross_entropy(jnp.zeros((b, t, v)), labels, v)
    np.testing.assert_allclose(float(xent_u), np.log(v), rtol=1e-5)
