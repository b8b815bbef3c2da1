"""The shape-only count of the posterior-grid work, and the peaks table."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from bench.workcount import grid_posterior_work, least_seconds


def test_count_at_a_small_shape():
    # K = 2 workers, G = 3 grid points, N = 4 observations, by hand:
    # cells 2*3*4*9 = 216, per observation 2*4*8 = 64, per grid point 2*3*8 = 48
    ops, nbytes = grid_posterior_work(2, 3, 4)
    assert ops == 216 + 64 + 48
    # t, f, mask: 3*2*4; scalars 9*2; grid 3; output 2*2*3; float32
    assert nbytes == 4 * (24 + 18 + 3 + 12)


def test_count_ignores_padding_and_scales_with_the_fleet():
    a = grid_posterior_work(1000, 256, 32)
    b = grid_posterior_work(2000, 256, 32)
    assert b[0] == 2 * a[0]  # linear in K: no per-launch term
    assert b[1] == 2 * a[1] - 4 * 256  # the grid is read once


def test_least_seconds_names_its_bound():
    assert least_seconds(2e12, 1e6, 1e12, 1e9) == (2.0, "compute")
    assert least_seconds(1e6, 2e9, 1e12, 1e9) == (2.0, "memory")


def test_cells_per_evaluation_match_the_reference_shapes():
    """Every (k, g, n) cell of the reference's residual tables is counted."""
    from bench.reference.grid import exponent_grid, log_posteriors

    k, g, n = 3, 8, 5
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.uniform(0.5, 2, (k, n)), jnp.float32)
    f = jnp.asarray(rng.uniform(0.1, 0.9, (k, n)), jnp.float32)
    out = log_posteriors(exponent_grid(g), t, f, jnp.ones((k, n)), jnp.ones(k),
                         jnp.ones(k), jnp.full(k, .5), jnp.full(k, .5),
                         (jnp.full(k, 2.), jnp.full(k, 2.)),
                         (jnp.full(k, 2.), jnp.full(k, 2.)))
    assert out.shape == (k, 2, g)
    ops, _ = grid_posterior_work(k, g, n)
    assert ops >= 9 * k * g * n


def test_peaks_are_keyed_by_device_kind():
    peaks = common.peaks()
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e == {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11,
                   "hbm_bytes": 1.6e10}
    with pytest.raises(KeyError):
        peaks["devices"]["cpu"]
