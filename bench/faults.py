"""Faults planted in the program under the timed path, and the control.

Each entry takes a ``setattr(owner, name, value)``, such as
``pytest.MonkeyPatch.setattr``, and plants itself in the program; a run of a
cell with it planted has to read ``correct`` false.  ``bench/calibrate.py``
reads the numbers each one gives at a cell's own size on the chip, and
``bench/tests/test_correctness.py`` drives a whole run with each.  Clear
JAX's caches after planting, so that the programs are traced anew.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaves(g) -> dict:
    return dict(
        mu0=g.ng.mu0, kappa0=g.ng.kappa0, nu0=g.ng.nu0, psi0=g.ng.psi0,
        aa=g.alpha_prior.a, ab=g.alpha_prior.b, ba=g.beta_prior.a,
        bb=g.beta_prior.b, mu=g.mu, lam=g.lam, alpha=g.alpha, beta=g.beta,
        key=g.key)


def _state(g, s: dict):
    return g._replace(
        ng=g.ng._replace(mu0=s["mu0"], kappa0=s["kappa0"], nu0=s["nu0"],
                         psi0=s["psi0"]),
        alpha_prior=g.alpha_prior._replace(a=s["aa"], b=s["ab"]),
        beta_prior=g.beta_prior._replace(a=s["ba"], b=s["bb"]),
        mu=s["mu"], lam=s["lam"], alpha=s["alpha"], beta=s["beta"], key=s["key"])


def _advance(setattr, advance) -> None:
    """Put ``advance`` in the place of the program's one fleet-advance path."""
    from repro.sched import dag
    from repro.serve import service

    setattr(service, "advance_fleet", advance)
    setattr(dag, "advance_fleet", advance)


def control(setattr) -> None:
    """The reference drain in the program's place, its posterior grid in
    bfloat16: one precision below the float32 the configurations state."""
    from .reference import gibbs as ref_gibbs

    def advance(fleet, times, fracs, config, mask=None, active_idx=None):
        m = jnp.ones_like(times) if mask is None else \
            jnp.broadcast_to(mask, times.shape).astype(times.dtype)
        out = ref_gibbs.drain(_leaves(fleet), times, fracs, m,
                              n_iters=config.n_iters, grid_size=config.grid_size,
                              rho=config.discount, grid_dtype=jnp.bfloat16)
        return _state(fleet, out), jnp.zeros(times.shape[:-1], times.dtype)

    _advance(setattr, advance)


def unchanged(setattr) -> None:
    """A drain that returns the state it was given."""
    _advance(setattr, lambda fleet, times, *a, **k:
             (fleet, jnp.zeros(times.shape[:-1], times.dtype)))


def half_batch(setattr) -> None:
    """A drain that leaves out the second half of each worker's rows."""
    from repro.sched import scheduler

    real = scheduler.advance_fleet

    def advance(fleet, times, fracs, config, mask=None, active_idx=None):
        m = jnp.ones_like(times) if mask is None else \
            jnp.broadcast_to(mask, times.shape).astype(times.dtype)
        keep = jnp.cumsum(m, axis=-1) <= 0.5 * jnp.sum(m, axis=-1, keepdims=True)
        return real(fleet, times, fracs, config, mask=m * keep,
                    active_idx=active_idx)

    _advance(setattr, advance)


def _alter(fr):
    """Every share scaled by exp(z / 2), z ~ N(0, 1) from a fixed key, and
    renormalised over the live shares."""
    z = jax.random.normal(jax.random.PRNGKey(0), fr.shape)
    fr = fr * jnp.exp(0.5 * z)
    return fr / jnp.sum(fr, axis=-1, keepdims=True)


def altered_answer(setattr) -> None:
    """The published split altered where the solve produces it."""
    from repro.sched import dag
    from repro.serve import service

    def wrap(solve):
        return lambda *a, **k: (lambda fr, st: (_alter(fr), st))(*solve(*a, **k))

    setattr(service, "solve_fractions", wrap(service.solve_fractions))
    setattr(dag, "solve_fractions", wrap(dag.solve_fractions))


def solve_skipped(setattr) -> None:
    """The solve's descent left out (no steps): it publishes the better of
    its floored uniform and equalising starts.  Not a fault for ``correct``
    (the split check holds the published split to those two); the bound on
    ``makespan_ratio`` is what prices it."""
    from repro.sched import dag
    from repro.serve import service

    def wrap(solve):
        return lambda *a, **k: solve(*a, **dict(k, steps=0))

    setattr(service, "solve_fractions", wrap(service.solve_fractions))
    setattr(dag, "solve_fractions", wrap(dag.solve_fractions))


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered_answer": altered_answer}
CAUGHT_BY = {"control": "posterior_gap", "unchanged": "posterior_gap",
             "half_batch": "posterior_gap", "altered_answer": "split_excess"}
# Changes that ``correct`` does not judge, read by ``bench/calibrate.py``
# for what they do to ``makespan_ratio``.
QUALITY = {"solve_skipped": solve_skipped}
