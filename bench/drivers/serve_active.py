"""Driver of a flat fleet served with an active set (``ServeConfig.active_size``).

The beat is ``serve_loop``'s: rows made from the published split, pushed
one by one, one tick; the rows of the next beat are made on background
threads while this one pushes and ticks (``AheadFleet``), so that the beat
is the service's and not the generator's at K = 10^5 (6.4M normal draws a
beat).  Each drain gives M workers a full grid evaluation and advances the
rest through the grid-free surrogate, so the check also knows which path
each worker took.  At the drain it compares, the driver keeps what the
selection read before the drain (``refresh_age``, ``ng.nu0``, ``ewma_ll``,
``live``) and the ``refresh_age`` after it: the program's active set is the
workers whose age the drain set to 0.  ``refresh_age`` is read again after
the flush, never inside the window.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..generator import Fleet
from ..reference import active as ref_active
from ..reference import gibbs as ref_gibbs
from . import serve_loop
from .serve_loop import _copy

CHUNKS = 8  # noise streams a beat's rows are drawn from: part of the rows
THREADS = 4  # threads that make them: part of the speed only


def _selection_inputs(state):
    s = state.sched
    return (state.refresh_age, s.gibbs.ng.nu0, s.ewma_ll, s.live)


def active_set_mismatch(m: int, age, nu, anomaly, live, after) -> int:
    """Workers whose membership in the program's active set (``after == 0``)
    differs from the reference selection; a worker tied at the M-th priority
    with one beyond it is not counted."""
    chosen, pri = ref_active.select(m, age, nu, anomaly, live)
    chosen, pri = np.asarray(chosen), np.asarray(pri)
    at_m = np.sort(pri)[::-1][m - 1]
    tied = pri == at_m if np.sum(pri >= at_m) > m else np.zeros_like(chosen)
    return int(np.sum(((np.asarray(after) == 0) != chosen) & ~tied))


def path_gaps(cfg, pre, post, t, f, selected) -> tuple:
    """(gaps of the sampled workers that took the grid path, gaps of those
    that took the surrogate), each against the reference of its path."""
    sc = cfg["sched"]
    want = ref_active.drain(
        pre, t, f, np.ones_like(t), selected, n_iters=int(sc["n_iters"]),
        grid_size=int(sc["grid_size"]), rho=float(sc["discount"]))
    gaps = np.asarray(ref_gibbs.gap(post, want))
    selected = np.asarray(selected)
    return gaps[selected], gaps[~selected]


class _Drawn:
    """Stands in for a generator whose next ``standard_normal`` is drawn."""

    def __init__(self, z):
        self.z = z

    def standard_normal(self, shape):
        assert self.z.shape == tuple(shape)
        return self.z


class AheadFleet(Fleet):
    """``Fleet`` whose next beat of rows is made on background threads while
    the beat before pushes and ticks.

    A beat's rows are ``Fleet.rows``'s law, split by row into ``CHUNKS``
    blocks, each drawn from its own stream spawned from the noise seed: they
    depend on the seed alone, not on the threads' timing, but they are not
    the one stream that ``Fleet.rows`` draws.  The threads make a beat's rows
    for the split the beat before was given; where that beat published
    another split, they are made again from the same draws.  Rows come back
    as float32.
    """

    def __init__(self, cfg: dict, traffic: dict, seeds: dict):
        super().__init__(cfg, traffic, seeds)
        streams = np.random.SeedSequence(seeds["noise"]).spawn(CHUNKS)
        self._streams = [np.random.default_rng(s) for s in streams]
        self._pool = ThreadPoolExecutor(THREADS)
        self._ahead = None  # (split, jobs, rows) of the next beat

    def _block(self, fracs, out, stream, z=None):
        """Fill ``out``, a block of rows, with the draws ``z`` or, where
        there are none yet, new draws from ``stream``; returns the draws."""
        if z is None:
            z = stream.standard_normal(out.shape)
        plain = copy.copy(self)
        plain._noise = _Drawn(z)
        out[...] = Fleet.rows(plain, fracs, len(out))
        return z

    def _start(self, fracs, n: int, zs=None):
        out = np.empty((n,) + self.shape, np.float32)
        jobs = [self._pool.submit(self._block, fracs, block, self._streams[i],
                                  None if zs is None else zs[i])
                for i, block in enumerate(np.array_split(out, CHUNKS))]
        return fracs, jobs, out

    def rows(self, fracs, n: int) -> np.ndarray:
        fracs = np.array(fracs)
        made, jobs, out = self._ahead or self._start(fracs, n)
        zs = [j.result() for j in jobs]
        if not np.array_equal(made, fracs):
            _, jobs, out = self._start(fracs, n, zs)
            for j in jobs:
                j.result()
        self._ahead = self._start(fracs, n)
        return out

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)
        self._ahead = None


class Driver(serve_loop.Driver):
    def __init__(self, cfg: dict, traffic: dict, seeds: dict, trace: bool):
        from repro import serve

        super().__init__(cfg, traffic, seeds, trace)
        self.fleet = AheadFleet(cfg, traffic, seeds)
        self.m = int(cfg["serve"]["active_size"])
        self.config = dataclasses.replace(self.config, active_size=self.m)
        del self.loop  # the base's dense loop, freed before the active one is made
        self.loop = serve.ServiceLoop(self.k, config=self.config,
                                      seed=seeds["program"])
        self.snap_active = None  # (selection inputs, refresh_age after)

    def beat(self, phase: str, index: int):
        snap = phase == "window" and index == self.snap_beat
        if phase == "warm":  # compiles the copies the window makes
            _copy(_selection_inputs(self.loop.state))
            _copy(self.loop.state.refresh_age)
        if snap:
            before = _copy(_selection_inputs(self.loop.state))
        out = super().beat(phase, index)
        if snap:
            self.snap_active = (before, _copy(self.loop.state.refresh_age))
        return out

    def ready(self) -> None:
        """Also collects and freezes what set-up left on the heap, so that a
        full collection in the window scans only what the window made: on
        the chip host one cost 40-60 ms and lengthened the beat it fell in."""
        super().ready()
        gc.collect()
        gc.freeze()

    def collect(self) -> None:
        self.fleet.close()
        end = np.asarray(self.loop.state.refresh_age)
        if self.snap_active is not None:
            (age, nu, anomaly, live), after = self.snap_active
            live = np.ones(self.k, np.float32) if live is None else np.asarray(live)
            self.snap_active = (np.asarray(age), np.asarray(nu),
                                np.asarray(anomaly), live, np.asarray(after))
            self.age_end = np.where(live > 0, end, 0)
        super().collect()

    def checks(self) -> dict:
        c = self.counters
        pre, post, t, f = self.snap
        age, nu, anomaly, live, after = self.snap_active
        grid, surrogate = path_gaps(self.cfg, pre, post, t, f,
                                    after[self.sample] == 0)
        both = len(grid) and len(surrogate)
        return {
            "ring_dropped": c["dropped"] + abs(c["pushes"] - self.rows_pushed),
            "drains_missed": self.beats - c["drains"],
            "posterior_gap": float(max(np.quantile(grid, 0.9),
                                       np.quantile(surrogate, 0.9))) if both else np.inf,
            "split_excess": self.split_excess(),
            "active_set_mismatch": active_set_mismatch(self.m, age, nu, anomaly,
                                                       live, after),
            "refresh_age_max": int(max(np.max(np.where(live > 0, after, 0)),
                                       np.max(self.age_end))),
        }

    def kernel_shape(self) -> dict:
        """What one beat asks of the kernel: ``n_iters`` launches on the
        M-worker slab; ``fleet`` is K, the workers the tick advances."""
        return dict(super().kernel_shape(), workers=self.m, fleet=self.k)
