"""The estimator as an always-on service: decoupled observe/propose cadence.

The paper's pitch is replacing offline controlled experiments with online
inference — but a synchronous observe->propose call chain is still the
offline posture: every caller blocks on a Gibbs sweep AND a simplex solve.
This module splits the two rates:

  * **observe on every drained batch** — telemetry lands in a
    ``TelemetryRing`` (push-mode, device-resident) and each ``tick`` drains
    the whole buffer through the fleet-native ``gibbs_batch`` via
    ``sched.advance_fleet`` (masked tail, identical semantics to
    ``sched.observe``);
  * **propose only when posteriors move** — a drift statistic (the
    symmetrized-KL metric, or the max per-worker ``hier.surprise`` when
    hierarchical pooling is on) gates the simplex solve (``lax.cond``)
    against a self-calibrating EWMA baseline (``repro.serve.gate``; a
    fixed ``drift_threshold`` remains available), with a hard
    ``max_staleness`` so a slowly-drifting fleet can never pin a stale
    split forever;
  * **readers never block** — the last-good fractions live in a
    double-buffered host slot (``ServiceLoop.fractions()``); a reader dips
    into whichever buffer is active while the ticker fills the other.

The whole per-tick program — drain, Gibbs update, drift test, conditional
solve — is ONE jitted function with the service state donated
(``donate_argnums``), so steady-state serving re-uses the state buffers in
place instead of allocating a fresh fleet posterior every batch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.frontier import UnitParams
from repro.sched.objectives import Objective
from repro.sched.scheduler import (
    ProposeStats,
    SchedulerConfig,
    SchedulerState,
    advance_fleet,
    solve_fractions,
    unit_params,
)
from repro.sched import scheduler as _sched
from repro.core.compress import select_active
from repro.hier.hyperprior import (
    Hyperprior,
    fit_hyperprior,
    hyper_init,
    shrink,
    _surprise_body,
)

from .gate import (
    DEFAULT_GATE_DECAY,
    DEFAULT_GATE_WARMUP,
    DEFAULT_GATE_Z,
    GateState,
    gate_init,
    gate_update,
)
from .ring import TelemetryRing, drain, push_rows, ring_init

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static service knobs; hashable, jit-static like ``SchedulerConfig``.

    The drift gate decides when ``tick`` re-solves the split.  With
    ``drift_threshold=None`` (the default) the gate is SELF-CALIBRATING:
    each tick's drift statistic is scored against an online EWMA baseline
    of its own steady-state level (``repro.serve.gate``), so the same
    configuration yields a stable skip rate at K = 10^2 and K = 10^4.
    Set ``drift_threshold`` to a float to keep the fixed-threshold PR 6
    behavior (the gate state is then never touched).

    The statistic itself depends on ``sched.hierarchical``: the legacy
    max-over-workers posterior KL (:func:`posterior_drift`) by default, or
    the max per-worker ``hier.surprise`` against the pooled fleet
    hyperprior when hierarchical pooling is on — the latter's per-worker
    null level does not grow with K.  ``max_staleness`` is the hard cap on
    drains between proposes either way, and owns proposing during the
    calibrated gate's ``gate_warmup`` ticks.
    """

    sched: SchedulerConfig = SchedulerConfig()
    capacity: int = 64  # ring slots buffered between drains
    drift_threshold: Optional[float] = None  # None = self-calibrating gate
    max_staleness: int = 8  # hard cap: drains between proposes
    gate_z: float = DEFAULT_GATE_Z  # z-score the calibrated gate fires at
    gate_warmup: int = DEFAULT_GATE_WARMUP  # stats observed before firing
    gate_decay: float = DEFAULT_GATE_DECAY  # EWMA decay of the baseline
    active_size: Optional[int] = None  # compressed-posterior active set: per
    # drain only the top-M workers (young / surprising / anomalous / stale —
    # ``core.compress.select_active``) run the full exponent-grid program;
    # the rest advance through the grid-free moment-matched surrogate.
    # None = dense legacy (every worker, every drain).
    async_propose: bool = False  # publish proposals asynchronously: the tick
    # only marks the propose (ref/staleness bookkeeping) and the
    # ``ServiceLoop`` dispatches the simplex solve OFF the tick path,
    # publishing into the double-buffered slot when the solve completes
    # (version bump preserved).  False = legacy in-tick synchronous solve.


class ServeState(NamedTuple):
    """Everything the service owns; one checkpointable pytree."""

    sched: SchedulerState  # fleet posteriors (K, ...) leaves
    ring: TelemetryRing  # buffered telemetry
    fractions: Array  # (K,) last-published split
    stats: ProposeStats  # frontier stats at the last propose
    ref: UnitParams  # posterior point estimates at the last propose
    staleness: Array  # int32, drains since the last propose
    n_drains: Array  # int32, lifetime non-empty drains
    n_proposes: Array  # int32, lifetime proposes
    last_drift: Array  # float32, drift measured at the last tick
    gate: GateState  # EWMA baseline of the drift statistic
    hyper: Hyperprior  # pooled fleet prior (refit every hyper_refit_every)
    hyper_age: Array  # int32, drains since the last hyperprior refit
    refresh_age: Optional[Array] = None  # (K,) int32, drains since each
    # worker's last full grid refresh; allocated only under
    # ``config.active_size`` (None = dense legacy, structurally unchanged)


class TickInfo(NamedTuple):
    """Per-tick observability (scalars, cheap to host-sync)."""

    proposed: Array  # bool: did this tick re-solve the split?
    drift: Array  # float32 gate statistic (KL drift or max surprise)
    drained: Array  # int32 observations consumed from the ring
    fired: Array  # bool: the drift gate fired (else a propose is the
    # staleness cap's)
    refreshed: Array  # int32 workers given a full grid evaluation: K on a
    # dense drain, M on an active-set drain, 0 on an empty tick
    oldest: Array  # int32 largest ``refresh_age`` among live workers after
    # the tick (0 on the dense path, where every drain refreshes everyone)


def posterior_drift(ref: UnitParams, cur: UnitParams) -> Array:
    """How far the fleet's posterior point estimates moved; scalar >= 0.

    Per worker: the symmetrized KL divergence between the completion-time
    Normals N(mu_ref, sigma_ref^2) and N(mu_cur, sigma_cur^2) — scale-free,
    so a 10ms shift matters on a 50ms worker and vanishes on a 5s one —
    plus the squared shifts of the exponent posterior means (alpha, beta
    live in [0, 1]; weight 4 makes a 0.15 exponent jump comparable to a
    one-sigma mean shift).  The fleet drift is the max over workers: one
    worker changing regime must trigger a re-solve even if the other 9999
    are steady.
    """
    s2r = ref.sigma**2 + 1e-12
    s2c = cur.sigma**2 + 1e-12
    d2 = (ref.mu - cur.mu) ** 2
    kl_sym = 0.25 * ((s2r + d2) / s2c + (s2c + d2) / s2r) - 0.5
    expo = (ref.alpha - cur.alpha) ** 2 + (ref.beta - cur.beta) ** 2
    return jnp.max(kl_sym + 4.0 * expo)


@functools.partial(jax.jit, static_argnames=("config", "num_workers"))
def init(config: ServeConfig, num_workers: int, key: Array) -> ServeState:
    """Fresh service state: empty ring, uniform split, max staleness.

    Staleness starts saturated so the FIRST data-carrying tick always
    proposes — the uniform placeholder split is published, never trusted.
    """
    sched_state = _sched.init(config.sched, num_workers, key)
    k = num_workers
    return ServeState(
        sched=sched_state,
        ring=ring_init(config.capacity, num_workers),
        fractions=jnp.full((k,), 1.0 / k, jnp.float32),
        stats=ProposeStats(
            e_t=jnp.asarray(jnp.inf, jnp.float32),
            var=jnp.asarray(jnp.inf, jnp.float32),
            score=jnp.asarray(jnp.inf, jnp.float32),
        ),
        ref=unit_params(sched_state),
        staleness=jnp.asarray(config.max_staleness, jnp.int32),
        n_drains=jnp.zeros((), jnp.int32),
        n_proposes=jnp.zeros((), jnp.int32),
        last_drift=jnp.zeros((), jnp.float32),
        gate=gate_init(),
        # Global prior as a structurally-stable hyperprior placeholder
        # (canonical float32 so both lax.cond refit branches agree), with
        # the age saturated so the first data tick refits immediately.
        hyper=jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32),
            hyper_init(config.sched.mu_guess),
        ),
        hyper_age=jnp.asarray(config.sched.hyper_refit_every, jnp.int32),
        # Ages start saturated so the first drains cycle every worker
        # through a full grid refresh before any surrogate is trusted.
        refresh_age=(
            None
            if config.active_size is None
            else jnp.full((k,), 1_000_000, jnp.int32)
        ),
    )


@functools.partial(jax.jit, static_argnames=("config",))
def solve_published(
    cur: UnitParams,
    config: ServeConfig = ServeConfig(),
    live: Optional[Array] = None,
) -> Tuple[Array, ProposeStats]:
    """The publish-grade simplex solve, as its own dispatchable program.

    Exactly the solve the synchronous tick runs inline; split out so
    ``async_propose`` can launch it OFF the tick path (JAX dispatch is
    asynchronous — the call returns as soon as the program is enqueued) and
    publish on completion.
    """
    with jax.named_scope("solve"):
        fr, st = solve_fractions(
            cur,
            objective=config.sched.objective,
            steps=config.sched.opt_steps,
            lr=config.sched.opt_lr,
            num_points=config.sched.num_points,
            min_fraction=config.sched.min_fraction,
            live=live,
        )
    return fr.astype(jnp.float32), ProposeStats(
        e_t=st.e_t.astype(jnp.float32),
        var=st.var.astype(jnp.float32),
        score=st.score.astype(jnp.float32),
    )


def _tick_body(
    state: ServeState, config: ServeConfig
) -> Tuple[ServeState, TickInfo, UnitParams]:
    """One service beat: drain -> observe -> drift-gated propose.

    An empty ring is a true no-op on the beliefs (the Gibbs advance is
    skipped under ``lax.cond``, so not even the PRNG key moves); the
    propose branch runs only on posterior drift or staleness expiry.
    Also returns the post-advance point estimates so the async shell can
    hand them to the off-path solve without re-deriving them.
    """
    drained = state.ring.count
    has_data = drained > 0
    batch, ring = drain(state.ring)

    # -- active-set selection (static branch; shapes fixed by active_size) --
    k = state.fractions.shape[0]
    active_idx = None
    refresh_age = state.refresh_age
    refreshed = jnp.where(has_data, k, 0).astype(jnp.int32)
    if config.active_size is not None and config.active_size < k:
        m = config.active_size
        with jax.named_scope("active_select"):
            active_idx, _ = select_active(
                m,
                age=state.refresh_age,
                nu=state.sched.gibbs.ng.nu0,
                surprise=(
                    _surprise_body(state.sched.gibbs, state.hyper)
                    if config.sched.hierarchical
                    else None
                ),
                anomaly=state.sched.ewma_ll,
                live=state.sched.live,
            )
            refresh_age = jnp.where(
                has_data,
                (state.refresh_age + 1).at[active_idx].set(0),
                state.refresh_age,
            )
        refreshed = jnp.where(has_data, m, 0).astype(jnp.int32)
        live_age = (
            refresh_age
            if state.sched.live is None
            else jnp.where(state.sched.live > 0, refresh_age, 0)
        )
        oldest = jnp.max(live_age)
    else:  # dense: every drain refreshes every worker
        oldest = jnp.zeros((), jnp.int32)

    def advance(sched_state):
        fleet, _ = advance_fleet(
            sched_state.gibbs,
            batch.times,
            batch.fracs,
            config.sched,
            mask=batch.mask,
            active_idx=active_idx,
        )
        return sched_state._replace(gibbs=fleet, step=sched_state.step + 1)

    with jax.named_scope("gibbs_advance"):
        new_sched = jax.lax.cond(
            has_data, advance, lambda s: s, state.sched
        )

    # -- gate statistic (static branch: config is jit-static) ---------------
    if config.sched.hierarchical:
        # Refit the pooled fleet prior every hyper_refit_every drains,
        # then score each worker against it; fleet drift = max surprise.
        refit_due = has_data & (
            state.hyper_age >= config.sched.hyper_refit_every
        )
        hyper = jax.lax.cond(
            refit_due,
            lambda _: fit_hyperprior(new_sched.gibbs),
            lambda _: state.hyper,
            None,
        )
        hyper_age = jnp.where(
            refit_due,
            jnp.zeros((), jnp.int32),
            state.hyper_age + has_data.astype(jnp.int32),
        )
        drift = jnp.max(_surprise_body(new_sched.gibbs, hyper)).astype(
            jnp.float32
        )
        # Mid-life shrinkage on the refit cadence (ROADMAP PR 7 follow-up):
        # drift is scored on the UN-shrunk posteriors (shrinking first would
        # blunt the very statistic that detects the drifter), then every
        # worker is blended toward the fresh pool, ESS-weighted — converged
        # workers barely move, cold/drifting ones are pulled in.
        new_sched = jax.lax.cond(
            refit_due,
            lambda s: s._replace(
                gibbs=shrink(
                    s.gibbs, hyper, strength=config.sched.hyper_strength
                )
            ),
            lambda s: s,
            new_sched,
        )
    else:
        hyper, hyper_age = state.hyper, state.hyper_age
        drift = posterior_drift(
            state.ref, unit_params(new_sched)
        ).astype(jnp.float32)

    cur = unit_params(new_sched)

    staleness = state.staleness + has_data.astype(jnp.int32)
    # -- gate decision (static branch on the configured threshold) ----------
    with jax.named_scope("drift_gate"):
        if config.drift_threshold is None:
            fire, gate = gate_update(
                state.gate,
                drift,
                z=config.gate_z,
                warmup=config.gate_warmup,
                decay=config.gate_decay,
                update=has_data,
            )
        else:
            gate = state.gate  # fixed threshold: the baseline is never touched
            fire = drift > config.drift_threshold
        fired = has_data & fire
        should = fired | (has_data & (staleness >= config.max_staleness))

    if config.async_propose:
        # The solve leaves the tick: only the bookkeeping happens here
        # (ref/staleness/counters); the shell dispatches ``solve_published``
        # and flips the double buffer when it completes.
        fractions, stats = state.fractions, state.stats
        ref = jax.tree_util.tree_map(
            lambda old, new: jnp.where(should, new, old), state.ref, cur
        )
        staleness = jnp.where(should, 0, staleness)
    else:

        def do_propose(_):
            fr, st = solve_published(cur, config, new_sched.live)
            return fr, st, cur, jnp.zeros((), jnp.int32)

        def skip(_):
            return state.fractions, state.stats, state.ref, staleness

        fractions, stats, ref, staleness = jax.lax.cond(
            should, do_propose, skip, None
        )

    new_state = ServeState(
        sched=new_sched,
        ring=ring,
        fractions=fractions,
        stats=stats,
        ref=ref,
        staleness=staleness,
        n_drains=state.n_drains + has_data.astype(jnp.int32),
        n_proposes=state.n_proposes + should.astype(jnp.int32),
        last_drift=drift,
        gate=gate,
        hyper=hyper,
        hyper_age=hyper_age,
        refresh_age=refresh_age,
    )
    return new_state, TickInfo(
        proposed=should, drift=drift, drained=drained, fired=fired,
        refreshed=refreshed, oldest=oldest,
    ), cur


@functools.partial(
    jax.jit, static_argnames=("config",), donate_argnums=(0,)
)
def tick(
    state: ServeState, config: ServeConfig = ServeConfig()
) -> Tuple[ServeState, TickInfo]:
    """One service beat (see ``_tick_body``).

    The input state is DONATED: its buffers are reused for the output state
    (zero-copy advance — a regression test pins the no-growth invariant).
    """
    new_state, info, _ = _tick_body(state, config)
    return new_state, info


@functools.partial(
    jax.jit, static_argnames=("config",), donate_argnums=(0,)
)
def tick_with_params(
    state: ServeState, config: ServeConfig = ServeConfig()
) -> Tuple[ServeState, TickInfo, UnitParams]:
    """``tick`` that also returns the post-advance point estimates.

    The async shell's entry: when ``info.proposed`` fires it hands the
    returned ``UnitParams`` straight to ``solve_published`` — no second
    derivation from (donated) state.
    """
    return _tick_body(state, config)


# Donated block write: the ring's slot buffers advance in place.  One trace
# per block height, a power of two up to the capacity.
_push_rows = jax.jit(push_rows, donate_argnums=(0,))


class ServiceLoop:
    """Imperative shell of the push-mode service: jit closures built ONCE.

    The loop owns a ``ServeState`` and two compiled entry points — a
    donated block write of the ring (``push_rows``) and the donated fused
    ``tick``; no request ever triggers a re-trace beyond the block heights.
    Published fractions live in a double-buffered host slot: ``fractions()``
    reads whichever buffer is active without taking a lock or touching a
    device, so request threads never wait on a Gibbs sweep
    (``docs/serving.md``).

    ``push`` stages rows in a host block of ``config.capacity`` rows.  The
    block reaches the ring as one transfer and one write (a flush) at the
    start of ``tick``, at a read of ``state`` or ``counters()``, and at once
    when a ring's worth of rows is staged, so the host never holds more than
    one ring of telemetry and overflow is counted on the device as before.

    ``state`` is the checkpointable pytree, with every pushed row in its
    ring — hand it to ``CheckpointManager.save`` and assign it back after
    restore.

    Every push, flush, tick and publication is a ``repro.obs`` span
    (``serve.*``, ``docs/serving.md`` "Observability") carrying ``beat``, the
    number of ticks before it: the pushes a tick drains share its beat, and
    so do their flush and the publication of the split it solves.
    """

    def __init__(
        self,
        num_workers: int,
        *,
        config: Optional[ServeConfig] = None,
        seed: int = 0,
        state: Optional[ServeState] = None,
    ):
        self.config = config or ServeConfig()
        self._state = (
            state
            if state is not None
            else init(self.config, num_workers, jax.random.PRNGKey(seed))
        )
        # Two host blocks of (fracs, times, valid) rows, filled in turn; a
        # block is refilled only once the flush that read it has completed.
        shape = (self.config.capacity,) + self._state.ring.times.shape[1:]
        self._blocks = [
            tuple(np.empty(shape, np.float32) for _ in range(3))
            for _ in range(2)
        ]
        self._block = 0  # the block being filled
        self._staged = 0  # rows staged in it
        self._staged_valid = False  # whether any of them carried ``valid``
        self._in_flight = [False, False]  # a flush may still read the block
        self._slots = [
            np.asarray(self._state.fractions).copy(),
            np.asarray(self._state.fractions).copy(),
        ]
        self._active = 0
        self._version = 0
        self._pending: Optional[Tuple[Array, ProposeStats]] = None
        self._pending_beat = 0  # the beat whose tick dispatched _pending
        self._beat = 0  # ticks so far
        self._host = dict(rows_drained=0, proposes_gate=0, proposes_stale=0,
                          grid_refreshed=0, flushes=0)

    @property
    def state(self) -> ServeState:
        """The service state, with every pushed row written into its ring."""
        self._flush()
        return self._state

    @state.setter
    def state(self, value: ServeState) -> None:
        self._flush()  # staged rows belong to the state they were pushed to
        if any(self._in_flight):
            jax.block_until_ready(self._state.ring)
            self._in_flight = [False, False]
        self._state = value

    # -- ingestion (producer side) -----------------------------------------
    def push(self, fracs, times, valid=None) -> None:
        """Stage one telemetry row on the host (a float32 copy); returns at
        once.  ``valid`` optionally marks elements invalid, as in
        ``ring.push``."""
        with obs.span("serve.push", beat=self._beat):
            i = self._staged
            f, t, v = self._blocks[self._block]
            f[i] = fracs
            t[i] = times
            if valid is not None:
                if not self._staged_valid:
                    v[:i] = 1.0
                    self._staged_valid = True
                v[i] = valid
            elif self._staged_valid:
                v[i] = 1.0
            self._staged = i + 1
            if self._staged == self.config.capacity:
                self._flush()

    def _flush(self) -> None:
        """Write the staged rows into the ring: one transfer of the block,
        padded to a power of two, and one donated ``push_rows``."""
        n = self._staged
        if n == 0:
            return
        with obs.span("serve.flush", beat=self._beat, rows=n):
            other = 1 - self._block
            if self._in_flight[other]:  # the ring is the output of its flush
                jax.block_until_ready(self._state.ring)
                self._in_flight[other] = False
            height = min(1 << (n - 1).bit_length(), self.config.capacity)
            f, t, v = self._blocks[self._block]
            staged = jax.device_put((
                f[:height], t[:height], np.int32(n),
                v[:height] if self._staged_valid else None,
            ))
            ring = _push_rows(self._state.ring, *staged)
            self._state = self._state._replace(ring=ring)
        self._in_flight[self._block] = True
        self._block = other
        self._staged = 0
        self._staged_valid = False
        self._host["flushes"] += 1

    # -- the service beat (estimator side) ---------------------------------
    def tick(self) -> TickInfo:
        """Drain + observe (+ propose iff the posterior moved); publish.

        With ``config.async_propose`` the solve never runs inside this call:
        a fired gate dispatches ``solve_published`` (async JAX dispatch —
        enqueue and return) and each subsequent beat polls for completion,
        publishing into the inactive buffer and bumping ``version`` exactly
        as the synchronous path does.  A solve already in flight suppresses
        re-dispatch; the gate refires on a later beat if drift persists.

        The staged rows are flushed into the ring first.  The tick waits on
        the device once, for its flags and tallies; by then every flush
        before it has completed.
        """
        beat = self._beat
        with obs.span("serve.tick", beat=beat) as sp:
            self._flush()
            if self.config.async_propose:
                self.poll()
                self._state, info, cur = tick_with_params(
                    self._state, self.config
                )
            else:
                self._state, info = tick(self._state, self.config)
            with obs.span("serve.wait"):  # the fleet stays on the device
                flags = jax.device_get(
                    (info.proposed, info.fired, info.drained,
                     info.refreshed, info.oldest)
                )
            self._in_flight = [False, False]
            proposed, fired = bool(flags[0]), bool(flags[1])
            drained, refreshed, oldest = (int(x) for x in flags[2:])
            sp.set(proposed=proposed, fired=fired, drained=drained,
                   refreshed=refreshed, oldest=oldest)
            self._tally(proposed, fired, drained, refreshed)
            if proposed and not self.config.async_propose:
                self._publish(self._state.fractions, beat)
            elif proposed and self._pending is None:
                with obs.span("serve.dispatch_solve"):
                    self._pending = solve_published(
                        cur, self.config, self._state.sched.live
                    )
                self._pending_beat = beat
        self._beat = beat + 1
        return info

    def _tally(
        self, proposed: bool, fired: bool, drained: int, refreshed: int
    ) -> None:
        """Add one tick to this loop's host tallies (``counters()``)."""
        self._host["rows_drained"] += drained
        self._host["grid_refreshed"] += refreshed
        if proposed:
            self._host["proposes_gate" if fired else "proposes_stale"] += 1

    def poll(self) -> bool:
        """Publish a completed async solve, if any; never blocks.

        Returns True iff a new split was published.  ``jax.Array.is_ready``
        is the non-blocking completion probe; an unfinished solve leaves
        everything untouched.
        """
        if self._pending is None:
            return False
        with obs.span("serve.poll", beat=self._beat):
            fr, st = self._pending
            if not fr.is_ready():
                return False
            self._pending = None
            self._state = self._state._replace(fractions=fr, stats=st)
            self._publish(fr, self._pending_beat)
        return True

    def _publish(self, fractions, beat: int) -> None:
        with obs.span("serve.publish", beat=beat):
            inactive = 1 - self._active
            self._slots[inactive][:] = np.asarray(fractions)
            self._active = inactive  # atomic flip: readers see old or new
            self._version += 1

    # -- publication (reader side; never blocks) ---------------------------
    def fractions(self) -> np.ndarray:
        """Last-good published split — a host read, no device, no lock."""
        return self._slots[self._active]

    @property
    def version(self) -> int:
        """Bumps once per accepted propose; readers can poll for change."""
        return self._version

    # -- observability ------------------------------------------------------
    def counters(self) -> dict:
        """Lifetime drain/propose/drop counters, read in one transfer, and
        this loop's host tallies: ``rows_drained``, the proposes of the
        gate and of the staleness cap (they sum to ``proposes`` for a loop
        started from a fresh state), and ``grid_refreshed``, the workers
        given a full grid evaluation summed over ticks, and ``flushes``, the
        block writes that carried pushed rows into the ring.  Staged rows
        are flushed first, so ``pushes`` counts every row pushed."""
        s = self.state
        drains, proposes, dropped, pushes = jax.device_get(
            (s.n_drains, s.n_proposes, s.ring.dropped, s.ring.total)
        )
        return {
            "drains": int(drains),
            "proposes": int(proposes),
            "dropped": int(dropped),
            "pushes": int(pushes),
            **self._host,
        }

    @property
    def num_workers(self) -> int:
        return int(self._state.fractions.shape[0])
