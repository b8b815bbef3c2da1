"""Completion-time statistics of max-of-K and the efficient frontier (paper §1)."""
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.frontier import (
    UnitParams,
    completion_cdf,
    mean_var_completion,
    pareto_mask,
    sweep_two_way,
)
from repro.sched import Objective, optimal_two_way_fraction


def test_cdf_is_product_of_unit_cdfs():
    p = UnitParams.of([10.0, 20.0], [1.0, 2.0])
    fr = jnp.asarray([0.5, 0.5])
    eps = jnp.linspace(0.0, 30.0, 64)
    from repro.core.distributions import normal_cdf

    c1 = normal_cdf(eps, 0.5 * 10.0, 0.5 * 1.0)
    c2 = normal_cdf(eps, 0.5 * 20.0, 0.5 * 2.0)
    np.testing.assert_allclose(
        np.asarray(completion_cdf(eps, fr, p)), np.asarray(c1 * c2), rtol=1e-5
    )


def test_max_statistics_against_monte_carlo():
    rng = np.random.default_rng(0)
    p = UnitParams.of([30.0, 20.0], [2.0, 6.0])
    fr = jnp.asarray([0.4, 0.6])
    e, v = mean_var_completion(fr, p)
    x = rng.normal(0.4**1.0 * 30, 0.4**1.0 * 2, size=200_000)
    y = rng.normal(0.6**1.0 * 20, 0.6**1.0 * 6, size=200_000)
    mc = np.maximum(x, y)
    np.testing.assert_allclose(float(e), mc.mean(), rtol=1e-2)
    np.testing.assert_allclose(float(v), mc.var(), rtol=5e-2)


def test_mean_of_max_at_least_max_of_means():
    p = UnitParams.of([15.0, 10.0, 12.0], [1.0, 3.0, 2.0])
    fr = jnp.asarray([0.3, 0.4, 0.3])
    e, _ = mean_var_completion(fr, p)
    means = np.asarray([0.3 * 15, 0.4 * 10, 0.3 * 12])
    assert float(e) >= means.max() - 1e-3


def test_paper_illustration_frontier():
    """Paper Figs 1-2 hypothetical: mu_i=30 s_i=2, mu_j=20 s_j=6 — the curve
    is parabola-like and the min-mean point is interior."""
    p = UnitParams.of([30.0, 20.0], [2.0, 6.0])
    fg, mu_f, var_f = sweep_two_way(p, num_f=101)
    i = int(jnp.argmin(mu_f))
    assert 0.2 < float(fg[i]) < 0.6  # interior optimum
    # endpoints (all work on one unit) are worse than the optimum
    assert float(mu_f[0]) > float(mu_f[i])
    assert float(mu_f[-1]) > float(mu_f[i])
    # pareto frontier is non-empty and excludes dominated points
    mask = pareto_mask(mu_f, var_f)
    assert 0 < int(mask.sum()) < len(fg)
    mu_np, var_np = np.asarray(mu_f), np.asarray(var_f)
    for i_ in np.where(np.asarray(mask))[0]:
        dominated = np.any(
            (mu_np <= mu_np[i_]) & (var_np <= var_np[i_])
            & ((mu_np < mu_np[i_]) | (var_np < var_np[i_]))
        )
        assert not dominated


def test_objectives():
    p = UnitParams.of([30.0, 20.0], [2.0, 6.0])
    f_mean, mu_m, var_m = optimal_two_way_fraction(p, objective=Objective.mean())
    f_rav, mu_r, var_r = optimal_two_way_fraction(
        p, objective=Objective.mean_var(2.0)
    )
    # risk-averse point trades mean for variance
    assert float(var_r) <= float(var_m) + 1e-6
    assert float(mu_r) >= float(mu_m) - 1e-6
    f_con, mu_c, var_c = optimal_two_way_fraction(
        p, objective=Objective.variance_budget(float(var_m) * 0.5)
    )
    assert float(var_c) <= float(var_m) * 0.5 + 1e-4


def test_scaling_exponents_shift_optimum():
    """Sub-linear scaling (alpha<1) penalizes large fractions: the optimal
    split moves toward balance when overhead grows."""
    ideal = UnitParams.of([10.0, 10.0], [1.0, 1.0], [1.0, 1.0], [1.0, 1.0])
    f_i, _, _ = optimal_two_way_fraction(ideal)
    np.testing.assert_allclose(float(f_i), 0.5, atol=0.02)


def test_two_way_objective_floats_trace_once():
    """Only the objective's kind is static: three risk aversions share one
    trace of the solve, and each still moves the pick along the frontier."""
    obs.hook()
    p = UnitParams.of([30.0, 20.0], [2.0, 6.0])
    before = obs.snapshot()["traces"].get("_optimal_two_way", 0)
    # num_f no other test uses, so the first call here traces
    picks = [
        optimal_two_way_fraction(p, objective=Objective.mean_var(ra), num_f=57)
        for ra in (0.1, 1.0, 10.0)
    ]
    assert obs.snapshot()["traces"].get("_optimal_two_way", 0) - before == 1
    variances = [float(v) for _, _, v in picks]
    assert variances[0] >= variances[1] >= variances[2]
    assert variances[0] > variances[2]
