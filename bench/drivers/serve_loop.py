"""Driver of the flat fleet: ``repro.serve.ServiceLoop`` push / tick / fractions.

Each beat makes the cell's rows from the published split, pushes them one by
one, and beats the service once; a beat that proposes publishes.  The row's
push time is taken just before its ``push`` call, the publication's time when
``tick`` has returned with the new split in the host slot.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..common import span
from ..generator import Fleet
from ..reference import gibbs as ref_gibbs
from ..reference import makespan as ref_ms
from . import scheduler_config

_copy = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))


def chain_leaves(g, idx) -> dict:
    """The sampled rows of a fleet's Gibbs state, as the reference names them."""
    idx = jnp.asarray(idx)
    pick = lambda x: np.asarray(jnp.reshape(x, (-1,) + x.shape[g.mu.ndim:])[idx])
    return dict(
        mu0=pick(g.ng.mu0), kappa0=pick(g.ng.kappa0), nu0=pick(g.ng.nu0),
        psi0=pick(g.ng.psi0), aa=pick(g.alpha_prior.a), ab=pick(g.alpha_prior.b),
        ba=pick(g.beta_prior.a), bb=pick(g.beta_prior.b), mu=pick(g.mu),
        lam=pick(g.lam), alpha=pick(g.alpha), beta=pick(g.beta), key=pick(g.key),
    )


def posterior_gap(cfg, pre, post, t, f, grid_dtype=jnp.float32, mask=None):
    """(M,) per-worker gap between ``post`` and the reference drain of ``pre``."""
    sc = cfg["sched"]
    m = np.ones_like(t) if mask is None else mask
    want = ref_gibbs.drain(
        {k: jnp.asarray(v) for k, v in pre.items()},
        jnp.asarray(t), jnp.asarray(f), jnp.asarray(m, jnp.float32),
        n_iters=int(sc["n_iters"]), grid_size=int(sc["grid_size"]),
        rho=float(sc["discount"]), grid_dtype=grid_dtype)
    got = {k: jnp.asarray(v) for k, v in post.items()}
    return np.asarray(ref_gibbs.gap(got, want))


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seeds: dict, trace: bool):
        from repro import serve

        self.cfg = cfg
        self.trace = trace
        self.fleet = Fleet(cfg, traffic, seeds)
        self.k = int(cfg["workers"])
        self.rows_per_beat = self.fleet.rows_per_beat
        self.config = serve.ServeConfig(
            sched=scheduler_config(cfg["sched"]),
            capacity=int(cfg["serve"]["capacity"]),
            max_staleness=int(cfg["serve"]["max_staleness"]),
        )
        self.loop = serve.ServiceLoop(self.k, config=self.config,
                                      seed=seeds["program"])
        rng = np.random.default_rng(seeds["check"])
        lo, hi = cfg["check"]["snapshot_beat_range"]
        self.snap_beat = int(rng.integers(lo, hi + 1))
        m = min(int(cfg["check"]["sampled_workers"]), self.k)
        self.sample = np.sort(rng.choice(self.k, m, replace=False))
        self.snap = None
        self.pubs = []  # window publications: (fractions, belief, true mu)
        self.push_s = []  # host seconds per push call in the window
        self.beats = 0
        self.rows_pushed = 0

    # -- one beat ------------------------------------------------------------
    def beat(self, phase: str, index: int):
        fr = self.loop.fractions()
        with span("generate", self.trace):
            times = self.fleet.rows(fr, self.rows_per_beat).astype(np.float32)
        snap = phase == "window" and index == self.snap_beat
        if phase == "warm":  # compiles the copies the window makes
            _copy(self.loop.state.sched.gibbs)
            _copy(self.loop.state.ref)
        if snap:
            pre = _copy(self.loop.state.sched.gibbs)
        push_times = []
        for row in times:
            t0 = time.perf_counter()
            push_times.append(t0)
            with span("push", self.trace):
                self.loop.push(fr, row)
            if phase == "window":
                self.push_s.append(time.perf_counter() - t0)
        with span("tick", self.trace):
            info = self.loop.tick()
        published = time.perf_counter() if bool(info.proposed) else None
        self.beats += 1
        self.rows_pushed += len(times)
        if snap:
            self.snap = (pre, _copy(self.loop.state.sched.gibbs), times,
                         np.array(fr))
        if published is not None and phase == "window":
            self.pubs.append((np.array(self.loop.fractions()),
                              _copy(self.loop.state.ref), self.fleet.mu.copy()))
        return push_times, published

    def ready(self) -> None:
        jax.block_until_ready(self.loop.state)

    # -- after the window ----------------------------------------------------
    def memory_devices(self):
        return list(self.loop.state.fractions.devices())

    def collect(self) -> None:
        """Read back what the checks need, then free the service's state."""
        c = self.loop.counters()
        self.counters = c
        if self.snap is None:
            raise RuntimeError(f"the window ended before beat {self.snap_beat}, "
                               "whose drain the check compares")
        pre, post, times, fr = self.snap
        idx = self.sample
        self.snap = (chain_leaves(pre, idx), chain_leaves(post, idx),
                     times[:, idx].T.copy(), np.broadcast_to(fr[idx, None],
                                                             (len(idx), len(times))).copy())
        self.pubs = [(f, {k: np.asarray(v) for k, v in b._asdict().items()}, mu)
                     for f, b, mu in self.pubs]
        del self.loop

    def makespan_ratio(self) -> float:
        q = int(self.cfg["makespan_quad_points"])
        fl = self.fleet
        uni = np.full(self.k, 1.0 / self.k, np.float32)
        if not self.pubs:
            return np.nan
        ratios = []
        for f, _, mu in self.pubs:
            e = lambda x: float(ref_ms.expected_makespan(
                jnp.asarray(x, jnp.float32), jnp.asarray(mu, jnp.float32),
                jnp.asarray(fl.sigma, jnp.float32), jnp.asarray(fl.alpha, jnp.float32),
                jnp.asarray(fl.beta, jnp.float32), num_points=q))
            ratios.append(e(f) / e(uni))
        return float(np.mean(ratios))

    def split_excess(self) -> float:
        """Worst relative excess of a published split's E[T] over the better
        of the reference's candidates, under the belief it was solved from."""
        sc = self.cfg["sched"]
        if not self.pubs:
            return np.inf  # a window with no publication to check fails
        worst = -np.inf
        for f, b, _ in self.pubs:
            belief = {k: jnp.asarray(v, jnp.float32) for k, v in b.items()}
            e = lambda x: float(ref_ms.expected_makespan(
                jnp.asarray(x, jnp.float32), belief["mu"], belief["sigma"],
                belief["alpha"], belief["beta"], num_points=int(sc["num_points"])))
            uni, eq = ref_ms.candidates(belief, float(sc["min_fraction"]))
            best = min(e(uni), e(eq))
            worst = max(worst, e(f) / best - 1.0)
        return float(worst)

    def checks(self) -> dict:
        c = self.counters
        pre, post, t, f = self.snap
        return {
            "ring_dropped": c["dropped"] + abs(c["pushes"] - self.rows_pushed),
            "drains_missed": self.beats - c["drains"],
            "posterior_gap": float(np.quantile(posterior_gap(self.cfg, pre, post, t, f), 0.9)),
            "split_excess": self.split_excess(),
        }

    def kernel_shape(self) -> dict:
        """What one beat asks of the posterior-grid kernel, from shapes alone."""
        sc = self.cfg["sched"]
        return dict(workers=self.k, grid=int(sc["grid_size"]),
                    obs=self.rows_per_beat, launches=int(sc["n_iters"]))
