"""Invariants of microbatch quantization, incl. adversarial fraction vectors
and the batched on-device refinement."""
import numpy as np
import pytest

from repro import sched
from repro.core.frontier import UnitParams, mean_var_completion


def _check_invariants(counts, total, min_per_worker=1):
    assert counts.sum() == total
    assert (counts >= min_per_worker).all()


@pytest.mark.parametrize(
    "fracs,total", [([0.61, 0.29, 0.10], 16), ([0.4, 0.3, 0.2, 0.1], 32)]
)
def test_counts_sum_and_floor(fracs, total):
    counts = sched.quantize_fractions(np.array(fracs), total)
    _check_invariants(counts, total)
    assert (np.diff(counts) < 0).all()  # the fraction order survives


def test_min_per_worker_respected():
    fr = np.array([0.97, 0.01, 0.01, 0.01])
    counts = sched.quantize_fractions(fr, 12, min_per_worker=2)
    _check_invariants(counts, 12, min_per_worker=2)


def test_k_near_total_terminates():
    """K workers, total barely above K*min: the over-allocation shed loop
    must terminate and land exactly on the total."""
    k = 16
    fr = np.full(k, 1.0 / k)
    counts = sched.quantize_fractions(fr, k, min_per_worker=1)
    _check_invariants(counts, k)
    assert (counts == 1).all()

    counts = sched.quantize_fractions(fr, k + 1, min_per_worker=1)
    _check_invariants(counts, k + 1)


def test_near_zero_fractions_terminate():
    """Degenerate simplex corners: min_per_worker floors force shedding from
    the dominant worker without infinite-looping."""
    k = 8
    fr = np.zeros(k)
    fr[0] = 1.0  # everything on one worker
    counts = sched.quantize_fractions(fr, 10, min_per_worker=1)
    _check_invariants(counts, 10)
    assert counts[0] == 10 - (k - 1)

    fr = np.full(k, 1e-12)
    fr[3] = 1.0 - 7e-12
    counts = sched.quantize_fractions(fr, k, min_per_worker=1)
    _check_invariants(counts, k)


def test_random_adversarial_vectors():
    rng = np.random.default_rng(0)
    for _ in range(25):
        k = int(rng.integers(2, 12))
        total = int(rng.integers(k, 4 * k))
        # spiky dirichlet: most mass on few workers
        fr = rng.dirichlet(np.full(k, 0.05))
        counts = sched.quantize_fractions(fr, total)
        _check_invariants(counts, total)


def test_total_too_small_raises():
    with pytest.raises(ValueError):
        sched.quantize_fractions(np.array([0.5, 0.5]), 3, min_per_worker=2)


@pytest.mark.parametrize(
    "mu,sigma", [([10.0, 20.0, 40.0], [1.0, 2.0, 4.0]),
                 ([10.0, 40.0, 40.0], [1.0, 4.0, 4.0])]
)
def test_batched_refinement_improves_objective(mu, sigma):
    p = UnitParams.of(mu, sigma)
    fracs, _ = sched.solve_fractions(p)
    counts = sched.quantize_fractions(np.asarray(fracs), 8, p)
    _check_invariants(counts, 8)
    naive = np.array([3, 3, 2])

    def obj(c):
        import jax.numpy as jnp

        e, _ = mean_var_completion(jnp.asarray(c / 8.0, jnp.float32), p)
        return float(e)

    assert obj(counts) <= obj(naive) + 1e-6


def test_refinement_preserves_invariants():
    rng = np.random.default_rng(1)
    k = 6
    p = UnitParams.of(list(rng.uniform(5, 40, k)), list(rng.uniform(0.5, 3, k)))
    fr = rng.dirichlet(np.full(k, 0.2))
    for total, minw in ((k, 1), (13, 1), (24, 2)):
        counts = sched.quantize_fractions(
            fr, total, p, min_per_worker=minw
        )
        _check_invariants(counts, total, min_per_worker=minw)


# ---------------------------------------------------------------------------
# fleet-scale rounding (water-fill shed/top-up) and live-masked quantization
# ---------------------------------------------------------------------------
def test_large_fleet_rounding_invariants_and_proportionality():
    """The vectorized water-fill replaces the O(K^2 log K) greedy loops: at
    K in the thousands the invariants must hold and counts must track the
    real-valued allocation to within the one-unit rounding granularity."""
    rng = np.random.default_rng(2)
    # spiky fleets: invariants only (the min floor forces redistribution)
    for k, total in ((512, 4096), (2000, 2000), (2000, 6000)):
        fr = rng.dirichlet(np.full(k, 0.3))
        counts = sched.quantize_fractions(fr, total)
        _check_invariants(counts, total)
    # near-uniform fleet where the floor never binds: counts must track the
    # real-valued allocation to within the one-unit rounding granularity
    k = 4096
    fr = rng.dirichlet(np.full(k, 50.0))
    counts = sched.quantize_fractions(fr, 8 * k)
    _check_invariants(counts, 8 * k)
    assert np.max(np.abs(counts - fr * 8 * k)) <= 2.0


def test_large_fleet_rounding_deterministic():
    rng = np.random.default_rng(3)
    fr = rng.dirichlet(np.full(1024, 0.1))
    a = sched.quantize_fractions(fr, 8192)
    b = sched.quantize_fractions(fr, 8192)
    np.testing.assert_array_equal(a, b)


def test_spiky_large_fleet_sheds_to_floor():
    """One dominant worker at K=1000: shedding must pull thousands of units
    off it in one water-fill, not one unit per pass."""
    k = 1000
    fr = np.full(k, 1e-9)
    fr[7] = 1.0 - (k - 1) * 1e-9
    counts = sched.quantize_fractions(fr, k + 50)
    _check_invariants(counts, k + 50)
    assert counts[7] == 51  # everyone else pinned at the floor


def test_live_mask_zeroes_dead_and_preserves_invariants():
    rng = np.random.default_rng(4)
    k = 12
    fr = rng.dirichlet(np.full(k, 0.5))
    live = np.ones(k, bool)
    live[[2, 5, 9]] = False
    counts = sched.quantize_fractions(fr, 64, live=live)
    assert counts.shape == (k,)
    assert (counts[~live] == 0).all()
    assert counts.sum() == 64
    assert (counts[live] >= 1).all()


def test_live_mask_with_params_and_refinement():
    k = 6
    p = UnitParams.of([10.0, 20.0, 40.0, 15.0, 25.0, 30.0],
                      [1.0, 2.0, 4.0, 1.5, 2.5, 3.0])
    fr = np.full(k, 1.0 / k)
    live = np.asarray([True, True, False, True, True, False])
    counts = sched.quantize_fractions(fr, 24, p, live=live, min_per_worker=2)
    assert (counts[~live] == 0).all()
    assert counts.sum() == 24
    assert (counts[live] >= 2).all()


def test_all_live_mask_matches_no_mask():
    rng = np.random.default_rng(5)
    k = 10
    fr = rng.dirichlet(np.full(k, 0.4))
    a = sched.quantize_fractions(fr, 40)
    b = sched.quantize_fractions(fr, 40, live=np.ones(k, bool))
    np.testing.assert_array_equal(a, b)


def test_slab_refinement_improves_objective_at_scale():
    """Above the exact-sweep cutoff the donor/receiver slab refinement must
    still only ever improve the objective while keeping the invariants."""
    import jax.numpy as jnp

    rng = np.random.default_rng(6)
    k = 48  # > _REFINE_SLAB: slab path, not the exact K x K sweep
    p = UnitParams.of(list(rng.uniform(5, 50, k)), list(rng.uniform(0.5, 4, k)))
    fr = rng.dirichlet(np.full(k, 0.5))
    total = 480
    counts = sched.quantize_fractions(fr, total, p)
    _check_invariants(counts, total)

    def obj(c):
        e, _ = mean_var_completion(jnp.asarray(c / total, jnp.float32), p)
        return float(e)

    # naive proportional rounding (largest-remainder) as the no-refinement bar
    raw = fr * total
    naive = np.maximum(np.floor(raw).astype(int), 1)
    gap = total - naive.sum()
    order = np.argsort(raw - np.floor(raw))[::-1]
    for i in range(abs(gap)):
        naive[order[i % k]] += 1 if gap > 0 else -1
    assert obj(counts) <= obj(naive) + 1e-6
