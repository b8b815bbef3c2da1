"""The traffic generator: reproducible from the seed, SimulatedCluster's draws."""
import numpy as np
import pytest

from bench import common
from bench.generator import Fleet, sub_seeds

NAMES = ("layout", "noise")


def small(name, **over):
    cfg = common.config(name)
    if "workers" in cfg:
        cfg["workers"] = 40
    else:
        cfg["workers_per_stage"] = 6
    cfg.update(over)
    return cfg


@pytest.mark.parametrize("config,traffic", [
    ("borg-cell", "steady"), ("montage", "steady")])
def test_same_seed_same_rows(config, traffic):
    cfg, tr = small(config), common.traffic(traffic)
    big = 2**31 + 12345
    a, b = (Fleet(cfg, tr, sub_seeds(big, NAMES)) for _ in range(2))
    fr = np.full(a.shape, 1.0 / a.shape[-1])
    for _ in range(3):
        np.testing.assert_array_equal(a.rows(fr, 4), b.rows(fr, 4))
    np.testing.assert_array_equal(a.mu, b.mu)


@pytest.mark.parametrize("config", ["borg-cell", "montage"])
def test_seeds_share_one_set_of_speeds(config):
    cfg, tr = small(config), common.traffic("steady")
    a = Fleet(cfg, tr, sub_seeds(1, NAMES))
    b = Fleet(cfg, tr, sub_seeds(2, NAMES))
    assert not np.array_equal(a.mu, b.mu)
    np.testing.assert_array_equal(np.sort(a.mu, axis=-1), np.sort(b.mu, axis=-1))


def test_class_counts_follow_the_shares():
    cfg = common.config("borg-cell")
    fl = Fleet(cfg, common.traffic("steady"), sub_seeds(3, NAMES))
    assert fl.mu.shape == (12583,)
    counts = {c: int(np.sum(fl.mu == 1.0 / c)) for c in (0.25, 0.5, 1.0)}
    assert counts == {0.25: 126, 0.5: 11639, 1.0: 818}


def test_rows_are_simulated_cluster_draws():
    from repro.distributed.simulated_cluster import SimulatedCluster, WorkerSpec

    cfg = small("borg-cell")
    seeds = sub_seeds(4, NAMES)
    fl = Fleet(cfg, common.traffic("steady"), seeds)
    specs = [WorkerSpec(mu=m, sigma=s, alpha=a, beta=b)
             for m, s, a, b in zip(fl.mu, fl.sigma, fl.alpha, fl.beta)]
    cluster = SimulatedCluster(specs, seed=seeds["noise"])
    rng = np.random.default_rng(0)
    fr = rng.dirichlet(np.ones(fl.shape[0]))
    fr[3] = 0.0  # floored at 1e-6 by both
    want = np.stack([cluster.step_times(fr) for _ in range(5)])
    np.testing.assert_allclose(fl.rows(fr, 5), want, rtol=1e-12, atol=0)


def test_a_beat_fills_the_ring():
    for name in ("borg-cell", "montage"):
        cfg = common.config(name)
        fl = Fleet(small(name), common.traffic("steady"), sub_seeds(5, NAMES))
        assert fl.rows_per_beat == cfg["serve"]["capacity"] == 64


def test_montage_stage_work_and_widths():
    """A stage's work is its task count times its mean runtime; a stage of
    w < K tasks runs on its first w workers, on w fixed speed quantiles."""
    from bench.generator import stage_widths

    cfg = common.config("montage")
    fl = Fleet(cfg, common.traffic("steady"), sub_seeds(6, NAMES))
    widths = stage_widths(cfg)
    assert widths == (128, 128, 1, 1, 128, 17, 17, 16, 1)
    for s, w, mu in zip(cfg["stages"], widths, fl.mu):
        work = s["tasks"] * s["mean_runtime_s"]
        slow = np.sort(mu[:w] / work)
        q = (np.arange(w) + 0.5) / w
        np.testing.assert_allclose(slow, 10.0 ** (1.3 * q), rtol=1e-12)
        np.testing.assert_allclose(mu[w:], work)
