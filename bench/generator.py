"""The one traffic generator: a fleet's true speeds and its telemetry rows.

Everything is made from ``--seed`` alone.  The seed only reorders a fixed set
of sizes: every seed gets the same speed classes in the same counts (or the
same quantiles of a log-uniform spread), assigned to workers in another order,
so two seeds pose the same problem to the service.

A worker that processes a share ``f`` of a step takes
``t ~ N(f^alpha mu, (f^beta sigma)^2)``, floored at 1e-6: the semantics of
``repro.distributed.SimulatedCluster.step_times``, drawn for all workers and
rows at once (``SimulatedCluster`` loops over workers in Python).

A traffic mix is a data file under ``bench/traffic/``.  Before every beat
``ring_fill`` times the configuration's telemetry capacity (``serve.capacity``:
the rows one drain takes) of rows are made from the last published split.
"""
from __future__ import annotations

import numpy as np

FLOOR = 1e-6


def sub_seeds(seed: int, names) -> dict:
    """Independent 31-bit seeds, one per name, from one run seed of any size."""
    seq = np.random.SeedSequence(int(seed) % 2**128)
    kids = seq.spawn(len(names))
    return {
        n: int(k.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)
        for n, k in zip(names, kids)
    }


def _class_layout(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Per-worker mu of a flat fleet of speed classes, shuffled by the seed."""
    k = int(cfg["workers"])
    classes = cfg["speed_classes"]
    counts = [int(round(c["share"] * k)) for c in classes]
    counts[int(np.argmax(counts))] += k - sum(counts)
    mu = np.concatenate([
        np.full(n, cfg["mu_at_capacity_1"] / c["cpu_capacity"])
        for n, c in zip(counts, classes)
    ])
    return rng.permutation(mu)


def stage_widths(cfg: dict) -> tuple:
    """Live workers of each stage: its task count, at most the pool's size."""
    k = int(cfg["workers_per_stage"])
    return tuple(min(int(s["tasks"]), k) for s in cfg["stages"])


def _stage_layout(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """(S, K) mu of a staged workflow: the stage's work, its task count times
    the mean task runtime, times the worker's slowness.

    A stage of width w runs on its first w workers.  Their slowness takes w
    fixed quantiles of a log-uniform law over ``speed_spread_decades``, in an
    order of the seed's; the workers past w are never given work and hold
    the stage's work alone.
    """
    k = int(cfg["workers_per_stage"])
    mu = []
    for s, w in zip(cfg["stages"], stage_widths(cfg)):
        q = (np.arange(w) + 0.5) / w
        slowness = np.ones(k)
        slowness[:w] = rng.permutation(10.0 ** (q * float(cfg["speed_spread_decades"])))
        mu.append(s["tasks"] * s["mean_runtime_s"] * slowness)
    return np.stack(mu)


class Fleet:
    """True parameters of every worker and the rows they report."""

    def __init__(self, cfg: dict, traffic: dict, seeds: dict):
        layout = np.random.default_rng(seeds["layout"])
        if "stages" in cfg:
            self.mu = _stage_layout(cfg, layout)
        else:
            self.mu = _class_layout(cfg, layout)
        self.sigma = float(cfg["sigma_over_mu"]) * self.mu
        self.alpha = np.full(self.mu.shape, float(cfg["alpha"]))
        self.beta = np.full(self.mu.shape, float(cfg["beta"]))
        self.rows_per_beat = int(round(float(traffic["ring_fill"])
                                       * int(cfg["serve"]["capacity"])))
        self._noise = np.random.default_rng(seeds["noise"])

    @property
    def shape(self):
        return self.mu.shape

    def rows(self, fracs: np.ndarray, n: int) -> np.ndarray:
        """``n`` rows of completion times for the split ``fracs``: (n,) + shape.

        Row by row and worker by worker these are the draws that ``n`` calls
        of ``SimulatedCluster.step_times(fracs)`` make from the same generator.
        """
        f = np.maximum(np.asarray(fracs, np.float64), FLOOR)
        mean = f**self.alpha * self.mu
        std = f**self.beta * self.sigma
        z = self._noise.standard_normal((n,) + self.shape)
        return np.maximum(mean + std * z, FLOOR)
