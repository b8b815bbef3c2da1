"""Driver of a staged workflow: ``repro.sched.observe_dag`` / ``propose_dag``.

Each beat makes the cell's workflow runs from the published stage splits as
one (S, K, rows) block, hands it to ``observe_dag``, solves the next splits
with ``propose_dag`` and reads them back to the host: that read is the
publication.  A run's push time is taken when the driver starts to make the
block that carries it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..common import span
from ..generator import Fleet, stage_widths
from ..reference import makespan as ref_ms
from . import scheduler_config
from .serve_loop import chain_leaves, posterior_gap


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seeds: dict, trace: bool):
        from repro import sched

        self.sched = sched
        self.cfg = cfg
        self.trace = trace
        self.fleet = Fleet(cfg, traffic, seeds)
        self.s, self.k = self.fleet.shape
        self.rows_per_beat = self.fleet.rows_per_beat
        self.preds = tuple(tuple(int(p) for p in st["preds"]) for st in cfg["stages"])
        self.widths = stage_widths(cfg)
        self.live = np.arange(self.k)[None, :] < np.asarray(self.widths)[:, None]
        self.dag = sched.WorkflowDAG(preds=self.preds, num_workers=self.k,
                                     names=tuple(st["name"] for st in cfg["stages"]),
                                     stage_workers=self.widths)
        self.config = scheduler_config(cfg["sched"])
        self.state = sched.init_dag(self.config, self.dag,
                                    jax.random.PRNGKey(seeds["program"]))
        self.published = np.asarray(sched.uniform_fractions(self.dag), np.float32)
        self.rng = np.random.default_rng(seeds["check"])
        lo, hi = cfg["check"]["snapshot_beat_range"]
        self.snap_beat = int(self.rng.integers(lo, hi + 1))
        live = np.flatnonzero(self.live.reshape(-1))
        m = min(int(cfg["check"]["sampled_workers"]), live.size)
        self.sample = np.sort(self.rng.choice(live, m, replace=False))
        self.mc_key = jax.random.PRNGKey(seeds["quality"])
        self.snap = None
        self.pubs = []  # window publications: (fractions, true mu)
        # The publications whose belief the split check reads.
        self.beliefs = Sample(int(cfg["check"]["sampled_publications"]), self.rng)
        self.beats = 0

    def beat(self, phase: str, index: int):
        fr = self.published
        made = time.perf_counter()
        with span("generate", self.trace):
            times = np.moveaxis(self.fleet.rows(fr, self.rows_per_beat), 0, -1)
            block = self.sched.Telemetry(
                fracs=jnp.asarray(np.broadcast_to(fr[..., None], times.shape),
                                  jnp.float32),
                times=jnp.asarray(times, jnp.float32))
        with span("observe_dag", self.trace):
            state, _ = self.sched.observe_dag(self.state, block, self.config,
                                              dag=self.dag)
        with span("propose_dag", self.trace):
            fracs, _ = self.sched.propose_dag(state, self.dag, self.config)
        with span("publish", self.trace):
            self.published = np.asarray(fracs)
        published = time.perf_counter()
        if phase == "window" and index == self.snap_beat:
            self.snap = (self.state.gibbs, state.gibbs,
                         times.astype(np.float32), np.array(fr))
        if phase == "window":
            self.pubs.append((self.published, self.fleet.mu.copy()))
            self.beliefs.offer((self.published, state.gibbs))
        self.state = state
        self.beats += 1
        return [made] * self.rows_per_beat, published

    def ready(self) -> None:
        jax.block_until_ready(self.state)

    def memory_devices(self):
        return list(self.state.step.devices())

    def collect(self) -> None:
        self.steps = int(self.state.step)
        if self.snap is None:
            raise RuntimeError(f"the window ended before beat {self.snap_beat}, "
                               "whose drain the check compares")
        pre, post, times, fr = self.snap
        idx = self.sample
        n = times.shape[-1]
        self.snap = (chain_leaves(pre, idx), chain_leaves(post, idx),
                     times.reshape(-1, n)[idx],
                     np.broadcast_to(fr.reshape(-1)[idx, None], (len(idx), n)).copy())
        self.beliefs = [(f, _belief(g)) for f, g in
                        self.beliefs.items + [(self.published, self.state.gibbs)]]
        del self.state

    def makespan_ratio(self, chunk: int = 64) -> float:
        """Priced in chunks of a fixed size, so one program serves every run."""
        if not self.pubs:
            return np.nan
        fl = self.fleet
        n = len(self.pubs)
        pad = self.pubs + [self.pubs[-1]] * (-n % chunk)
        ratios = []
        for i in range(0, len(pad), chunk):
            f = jnp.asarray(np.stack([p for p, _ in pad[i:i + chunk]]), jnp.float32)
            mu = jnp.asarray(np.stack([m for _, m in pad[i:i + chunk]]), jnp.float32)
            b = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), mu.shape)
            cost = lambda split: np.asarray(ref_ms.workflow_makespan(
                self.mc_key, split, mu, b(fl.sigma), b(fl.alpha), b(fl.beta),
                b(self.live), preds=self.preds,
                num_samples=int(self.cfg["makespan_mc_samples"])))
            uni = self.live / self.live.sum(axis=-1, keepdims=True)
            ratios.append(cost(f) / cost(b(uni)))
        return float(np.mean(np.concatenate(ratios)[:n]))

    def split_excess(self) -> float:
        """Worst relative excess, over the sampled publications and the
        stages, of a published stage split's E[T] over the better of the
        reference's candidates for that stage, under the belief it was solved
        from."""
        sc = self.cfg["sched"]
        worst = -np.inf
        for f, b in self.beliefs:
            for s, w in enumerate(self.widths):
                belief = {k: jnp.asarray(v[s, :w], jnp.float32) for k, v in b.items()}
                e = lambda x: float(ref_ms.expected_makespan(
                    jnp.asarray(x, jnp.float32), belief["mu"], belief["sigma"],
                    belief["alpha"], belief["beta"], num_points=int(sc["num_points"])))
                uni, eq = ref_ms.candidates(belief, float(sc["min_fraction"]))
                worst = max(worst, e(f[s, :w]) / min(e(uni), e(eq)) - 1.0)
        return float(worst)

    def checks(self) -> dict:
        pre, post, t, f = self.snap
        return {
            "drains_missed": self.beats - self.steps,
            "posterior_gap": float(np.quantile(posterior_gap(self.cfg, pre, post, t, f), 0.9)),
            "split_excess": self.split_excess(),
        }

    def kernel_shape(self) -> dict:
        sc = self.cfg["sched"]
        return dict(workers=self.s * self.k, grid=int(sc["grid_size"]),
                    obs=self.rows_per_beat, launches=int(sc["n_iters"]))


class Sample:
    """A uniform sample of at most ``size`` of the items offered, drawn by
    ``rng`` (reservoir sampling): the publications a check reads."""

    def __init__(self, size: int, rng):
        self.size, self.rng, self.items, self.seen = size, rng, [], 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        slot = int(self.rng.integers(self.seen))
        if slot < self.size:
            self.items[slot] = item


def _belief(g) -> dict:
    """Posterior point estimates of a (S, K) Gibbs state, read to the host."""
    from ..reference.gibbs import belief

    leaves = dict(mu0=g.ng.mu0, nu0=g.ng.nu0, psi0=g.ng.psi0, aa=g.alpha_prior.a,
                  ab=g.alpha_prior.b, ba=g.beta_prior.a, bb=g.beta_prior.b)
    return {k: np.asarray(v) for k, v in belief(leaves).items()}
