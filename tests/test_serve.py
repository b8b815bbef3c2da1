"""The always-on serving loop (repro.serve): ring parity, cadence, memory.

The load-bearing claims:
  1. a sequence of ring drains advanced through ``gibbs_batch`` is BITWISE
     the synchronous ``gibbs.fit`` over the same observations — push-mode
     buffering changes when estimation runs, never what it computes;
  2. wrap-around and overflow preserve push order and mask exactly;
  3. the propose cadence fires on posterior drift (a worker changing
     regime), not on steady-state sampling noise;
  4. the donated tick/push path re-uses buffers: no per-step growth in
     live device arrays;
  5. the service state checkpoints and restores through CheckpointManager;
  6. rows staged on the host and written as one block reach the ring
     bitwise as the same rows pushed one by one.
"""
import gc
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, sched, serve
from repro.core import gibbs

N_ITERS, GRID = 3, 64


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _stream(n, seed=0):
    rng = np.random.default_rng(seed)
    f = rng.uniform(0.1, 0.9, n).astype(np.float32)
    t = (f**0.85 * 10.0 + f**0.8 * 0.5 * rng.standard_normal(n)).astype(np.float32)
    return t, f


# ---------------------------------------------------------------- ring parity
def test_ring_drains_bitwise_match_synchronous_fit():
    """N pushes + whole-batch drains == one synchronous ``fit``: bitwise."""
    cap = 32
    t, f = _stream(2 * cap)
    key = jax.random.PRNGKey(7)

    state = gibbs.init_state(key, mu_guess=10.0)
    ring = serve.ring_init(cap)
    for i in range(len(t)):
        ring = serve.push(ring, f[i], t[i])
        if (i + 1) % cap == 0:
            batch, ring = serve.drain(ring)
            state, _ = gibbs.gibbs_batch(
                state, batch.times, batch.fracs, batch.mask,
                n_iters=N_ITERS, grid_size=GRID,
            )

    ref, _ = gibbs.fit(
        key, jnp.asarray(t), jnp.asarray(f),
        batch_size=cap, n_iters=N_ITERS, grid_size=GRID, mu_guess=10.0,
    )
    assert _leaves_equal(state, ref)


def test_ring_wraparound_drain_is_bitwise_batch_sequence():
    """A drain that wraps the buffer still presents observations oldest-first
    with a masked tail — bitwise against hand-padded ``gibbs_batch`` calls
    over the same batch boundaries."""
    cap = 32
    t, f = _stream(20 + cap, seed=1)
    key = jax.random.PRNGKey(3)

    state = gibbs.init_state(key, mu_guess=10.0)
    ring = serve.ring_init(cap)
    for i in range(20):  # partial drain: head at 20, then wraps
        ring = serve.push(ring, f[i], t[i])
    batch, ring = serve.drain(ring)
    assert int(batch.count) == 20
    state, _ = gibbs.gibbs_batch(
        state, batch.times, batch.fracs, batch.mask,
        n_iters=N_ITERS, grid_size=GRID,
    )
    for i in range(20, 20 + cap):  # slots 20..31 then 0..19: wrapped
        ring = serve.push(ring, f[i], t[i])
    batch, ring = serve.drain(ring)
    np.testing.assert_array_equal(np.asarray(batch.times), t[20:])  # push order
    state, _ = gibbs.gibbs_batch(
        state, batch.times, batch.fracs, batch.mask,
        n_iters=N_ITERS, grid_size=GRID,
    )

    # reference: the same boundaries, hand-padded exactly like the ring pads
    ref = gibbs.init_state(key, mu_guess=10.0)
    t0 = np.concatenate([t[:20], np.full(12, 1.0, np.float32)])
    f0 = np.concatenate([f[:20], np.full(12, 0.5, np.float32)])
    m0 = np.concatenate([np.ones(20, np.float32), np.zeros(12, np.float32)])
    ref, _ = gibbs.gibbs_batch(
        ref, jnp.asarray(t0), jnp.asarray(f0), jnp.asarray(m0),
        n_iters=N_ITERS, grid_size=GRID,
    )
    ref, _ = gibbs.gibbs_batch(
        ref, jnp.asarray(t[20:]), jnp.asarray(f[20:]),
        jnp.ones(cap, jnp.float32), n_iters=N_ITERS, grid_size=GRID,
    )
    assert _leaves_equal(state, ref)


def test_ring_overflow_drops_oldest_and_counts():
    ring = serve.ring_init(4)
    for i in range(6):
        ring = serve.push(ring, 0.5, 10.0 + i)
    assert int(ring.dropped) == 2
    assert int(ring.total) == 6
    batch, ring = serve.drain(ring)
    # the two OLDEST entries (10, 11) were overwritten; order preserved
    np.testing.assert_array_equal(np.asarray(batch.times), [12.0, 13.0, 14.0, 15.0])
    np.testing.assert_array_equal(np.asarray(batch.mask), np.ones(4))
    assert int(ring.count) == 0


def test_fleet_ring_layout_and_validity_mask():
    """Fleet drains come out worker-major with per-element validity folded
    into the mask — the exact telemetry layout ``sched.observe`` accepts."""
    ring = serve.ring_init(3, num_workers=2)
    ring = serve.push(ring, [0.6, 0.4], [3.0, np.inf], valid=[1.0, 0.0])
    ring = serve.push(ring, [0.5, 0.5], [2.0, 4.0])
    batch, _ = serve.drain(ring)
    assert batch.times.shape == (2, 3)  # (K, capacity)
    np.testing.assert_array_equal(np.asarray(batch.times[0]), [3.0, 2.0, 1.0])
    np.testing.assert_array_equal(np.asarray(batch.mask), [[1, 1, 0], [0, 1, 0]])
    # the invalid inf never got stored (0 * inf = nan would leak)
    assert np.isfinite(np.asarray(batch.times)).all()


@pytest.mark.parametrize(
    "cap,before,buffered,n,height,with_valid",
    [
        (8, 0, 0, 1, 1, False),  # one row into an empty ring
        (8, 3, 4, 5, 8, True),  # head 7, 4 buffered: wraps, drops one
        (64, 40, 30, 64, 64, False),  # head 6, 30 buffered: drops 30
    ],
)
def test_push_rows_is_bitwise_sequential_pushes(
    cap, before, buffered, n, height, with_valid
):
    """A block write of n rows == n single-row pushes: slots, wrap,
    saturated count, dropped, total and the dummies of invalid elements;
    the block's padding rows are never written."""
    k = 3
    rng = np.random.default_rng(cap + n)
    rows = lambda m: (rng.uniform(0.1, 0.9, (m, k)).astype(np.float32),
                      rng.uniform(1.0, 9.0, (m, k)).astype(np.float32))
    ring = serve.ring_init(cap, num_workers=k)
    for f, t in zip(*rows(before)):
        ring = serve.push(ring, f, t)
    _, ring = serve.drain(ring)
    for f, t in zip(*rows(buffered)):
        ring = serve.push(ring, f, t)
    assert int(ring.head) == (before + buffered) % cap
    assert int(ring.count) == buffered

    f, t = rows(height)
    f[n:], t[n:] = np.nan, np.inf  # padding: must never reach the ring
    valid = None
    if with_valid:
        valid = (rng.uniform(size=(height, k)) > 0.3).astype(np.float32)
        t[0, 0], f[1, 2] = np.inf, np.nan
        valid[0, 0] = valid[1, 2] = 0.0
    want = ring
    for i in range(n):
        want = serve.push(want, f[i], t[i], None if valid is None else valid[i])
    got = jax.jit(serve.push_rows)(ring, f, t, n, valid)
    assert _leaves_equal(got, want)
    assert int(got.dropped) == max(0, buffered + n - cap)
    assert int(got.total) == before + buffered + n
    assert int(got.count) == min(cap, buffered + n)
    assert np.isfinite(np.asarray(got.times)).all()


# ------------------------------------------------------------------- cadence
def _steady_cfg(**kw):
    base = dict(
        sched=sched.SchedulerConfig(n_iters=4, grid_size=64, num_points=128,
                                    opt_steps=40, mu_guess=3.0),
        capacity=8, drift_threshold=0.25, max_staleness=100,
    )
    base.update(kw)
    return serve.ServeConfig(**base)


def _push_rounds(loop, mu, rounds, rng):
    fr = np.full(len(mu), 1.0 / len(mu), np.float32)
    infos = []
    for _ in range(rounds):
        for _ in range(loop.config.capacity):
            times = fr**0.9 * mu + fr**0.8 * 0.05 * mu * rng.standard_normal(len(mu))
            loop.push(fr, times.astype(np.float32))
        infos.append(loop.tick())
    return infos


def test_cadence_fires_on_drift_not_steady_state_noise():
    rng = np.random.default_rng(0)
    mu = np.array([2.0, 4.0, 6.0])
    loop = serve.ServiceLoop(3, config=_steady_cfg(), seed=2)

    infos = _push_rounds(loop, mu, 8, rng)
    assert bool(infos[0].proposed)  # saturated staleness: first drain solves
    late = [bool(i.proposed) for i in infos[4:]]
    assert not all(late), "steady-state sampling noise must not re-solve"

    v0 = loop.version
    mu_shift = mu * np.array([4.0, 1.0, 1.0])  # worker 0 changes regime
    infos = _push_rounds(loop, mu_shift, 2, rng)
    assert any(bool(i.proposed) for i in infos), "regime change must re-solve"
    assert max(float(i.drift) for i in infos) > loop.config.drift_threshold
    assert loop.version > v0  # the new split was published


@pytest.mark.no_host_sync
def test_empty_tick_is_noop_on_beliefs(host_staging):
    with host_staging():  # constructing the loop mints device state
        loop = serve.ServiceLoop(2, config=_steady_cfg(), seed=0)
        before = jax.tree_util.tree_map(
            lambda x: np.asarray(x).copy(), loop.state.sched
        )
    info = loop.tick()  # nothing buffered; guarded: no implicit transfers
    assert int(info.drained) == 0 and not bool(info.proposed)
    with host_staging():
        assert _leaves_equal(before, loop.state.sched)  # not even the PRNG moved
    assert loop.counters()["drains"] == 0


@pytest.mark.no_host_sync
def test_service_loop_learns_split_end_to_end(host_staging):
    """End-to-end split learning, with every ``tick`` (the production hot
    path: drain -> observe -> maybe-propose under one jit) running under
    ``jax.transfer_guard("disallow")`` — telemetry staging in ``push`` is
    the only sanctioned host edge."""
    rng = np.random.default_rng(1)
    mu = np.array([2.0, 8.0])  # worker 0 is 4x faster
    with host_staging():
        loop = serve.ServiceLoop(2, config=_steady_cfg(max_staleness=4), seed=3)
    fr_eq = np.full(2, 0.5, np.float32)
    for _ in range(10):
        with host_staging():  # host-side telemetry staging
            for _ in range(loop.config.capacity):
                times = (
                    fr_eq**0.9 * mu
                    + fr_eq**0.8 * 0.05 * mu * rng.standard_normal(2)
                )
                loop.push(fr_eq, times.astype(np.float32))
        loop.tick()  # guarded: the jitted path must stay on device
    fr = loop.fractions()
    assert fr[0] > fr[1]  # the fast worker carries more
    np.testing.assert_array_equal(fr, np.asarray(loop.state.fractions))
    c = loop.counters()
    assert c["drains"] == 10 and 1 <= c["proposes"] <= c["drains"]
    assert c["pushes"] == 10 * loop.config.capacity and c["dropped"] == 0


# -------------------------------------------------------------- observability
def test_host_tallies_split_proposes_and_count_drained_rows():
    """The gate's and the staleness cap's proposes sum to the device count,
    and every pushed row is drained."""
    rng = np.random.default_rng(5)
    mu = np.array([2.0, 4.0, 6.0])
    loop = serve.ServiceLoop(3, config=_steady_cfg(max_staleness=2), seed=1)
    _push_rounds(loop, mu, 6, rng)
    _push_rounds(loop, mu * np.array([4.0, 1.0, 1.0]), 2, rng)
    c = loop.counters()
    assert c["proposes_gate"] + c["proposes_stale"] == c["proposes"]
    assert c["proposes_gate"] >= 1 and c["proposes_stale"] >= 1
    assert c["rows_drained"] == c["pushes"] == 8 * loop.config.capacity
    assert c["drains"] == 8 and c["dropped"] == 0


def test_tick_records_carry_flags_and_share_a_beat_with_their_pushes():
    rng = np.random.default_rng(6)
    mu = np.array([2.0, 4.0])
    loop = serve.ServiceLoop(2, config=_steady_cfg(max_staleness=2), seed=2)
    obs.reset()
    infos = _push_rounds(loop, mu, 5, rng)
    spans = obs.snapshot()["spans"]
    ticks = [s for s in spans if s["name"] == "serve.tick"]
    assert [t["beat"] for t in ticks] == list(range(5))
    for t, info in zip(ticks, infos):
        assert t["attrs"] == {
            "proposed": bool(info.proposed),
            "fired": bool(info.fired),
            "drained": int(info.drained),
            "refreshed": int(info.refreshed),
            "oldest": int(info.oldest),
        }
        mine = [s for s in spans if s["beat"] == t["beat"]]
        pushes = [s for s in mine if s["name"] == "serve.push"]
        assert len(pushes) == t["attrs"]["drained"] == loop.config.capacity
        assert all(p["start_ns"] < t["start_ns"] for p in pushes)
        children = sorted(s["name"] for s in mine if s["parent"] == t["id"])
        want = ["serve.publish", "serve.wait"] if info.proposed else ["serve.wait"]
        assert children == want
    assert any(t["attrs"]["proposed"] for t in ticks)
    assert not all(t["attrs"]["proposed"] for t in ticks)


def test_ten_ticks_trace_the_tick_once():
    rng = np.random.default_rng(7)
    mu = np.array([2.0, 4.0])
    # a ring size no other test uses, so the first tick here traces
    loop = serve.ServiceLoop(2, config=_steady_cfg(capacity=11), seed=0)
    before = obs.snapshot()["traces"]
    _push_rounds(loop, mu, 10, rng)
    after = obs.snapshot()["traces"]
    assert after.get("tick", 0) - before.get("tick", 0) == 1
    assert after.get("push_rows", 0) - before.get("push_rows", 0) <= 1
    assert loop.counters()["drains"] == 10


# ------------------------------------------------------------ host staging
def _rows(n, k, seed):
    rng = np.random.default_rng(seed)
    f = rng.uniform(0.1, 0.9, (n, k)).astype(np.float32)
    t = (f**0.9 * 4.0 + 0.1 * rng.standard_normal((n, k))).astype(np.float32)
    return f, t


def _ref_push(loop, f, t, valid=None):
    """The reference: one row written straight into the loop's ring."""
    s = loop.state
    loop.state = s._replace(ring=serve.push(s.ring, f, t, valid))


def test_staged_pushes_overflow_bitwise_like_sequential_pushes():
    """C + 3 staged rows (some carrying ``valid``) and a tick: the same
    state and counters as the rows pushed one by one, three dropped."""
    cfg = _steady_cfg()
    cap, k = cfg.capacity, 3
    f, t = _rows(cap + 3, k, seed=8)
    valid = np.ones((cap + 3, k), np.float32)
    t[2, 1], valid[2, 1] = np.inf, 0.0
    loop = serve.ServiceLoop(k, config=cfg, seed=5)
    ref = serve.ServiceLoop(k, config=cfg, seed=5)
    for i in range(cap + 3):
        v = valid[i] if i in (2, cap + 1) else None
        loop.push(f[i], t[i], v)
        _ref_push(ref, f[i], t[i], v)
    i1, i2 = loop.tick(), ref.tick()
    assert _leaves_equal(i1, i2)
    assert _leaves_equal(loop.state, ref.state)
    c, want = loop.counters(), ref.counters()
    assert c.pop("flushes") == 2 and want.pop("flushes") == 0
    assert c == want
    assert c["dropped"] == 3 and c["pushes"] == cap + 3


def test_state_and_counters_read_mid_beat_see_staged_rows():
    cfg = _steady_cfg()
    k = 2
    f, t = _rows(7, k, seed=9)
    loop = serve.ServiceLoop(k, config=cfg, seed=6)
    ref = serve.ServiceLoop(k, config=cfg, seed=6)
    push = lambda rows: [(loop.push(f[i], t[i]), _ref_push(ref, f[i], t[i]))
                         for i in rows]
    push(range(3))
    assert loop.counters()["pushes"] == 3
    push(range(3, 5))
    assert int(loop.state.ring.count) == 5
    assert _leaves_equal(loop.state.ring, ref.state.ring)
    push(range(5, 7))
    assert int(loop.tick().drained) == int(ref.tick().drained) == 7
    assert _leaves_equal(loop.state, ref.state)
    assert loop.counters()["flushes"] == 3  # counters(), state, tick


def test_flush_program_traces_once_per_block_height():
    """Rows are shipped in blocks padded to a power of two: one trace per
    height, and the tick still traces once."""
    # a worker count no other test uses, so the first flushes here trace
    cfg = _steady_cfg(capacity=16)
    k = 7
    loop = serve.ServiceLoop(k, config=cfg, seed=0)
    ref = serve.ServiceLoop(k, config=cfg, seed=0)
    before = obs.snapshot()["traces"]
    seed = 0
    for n in (1, 2, 3, 4, 5, 8, 9, 16, 3, 16, 7):
        seed += 1
        f, t = _rows(n, k, seed)
        for i in range(n):
            loop.push(f[i], t[i])
            _ref_push(ref, f[i], t[i])
        assert int(loop.tick().drained) == int(ref.tick().drained) == n
    after = obs.snapshot()["traces"]
    assert after.get("push_rows", 0) - before.get("push_rows", 0) == 5
    assert after.get("tick", 0) - before.get("tick", 0) == 1
    assert _leaves_equal(loop.state, ref.state)


def test_flushes_count_one_per_data_carrying_tick():
    cfg = _steady_cfg()
    k = 2
    loop = serve.ServiceLoop(k, config=cfg, seed=1)
    obs.reset()
    for beat, n in enumerate((3, 0, cfg.capacity, 5)):
        f, t = _rows(n, k, seed=beat)
        for i in range(n):
            loop.push(f[i], t[i])
        loop.tick()
    c = loop.counters()
    assert c["flushes"] == c["drains"] == 3
    flushes = [s for s in obs.snapshot()["spans"] if s["name"] == "serve.flush"]
    assert [(s["beat"], s["attrs"]["rows"]) for s in flushes] == [
        (0, 3), (2, cfg.capacity), (3, 5)]


# ------------------------------------------------------------ donation/memory
def test_no_live_buffer_growth_across_ticks():
    """The donated push/tick path must re-use state buffers: the number of
    live device arrays is flat across service cycles (no per-step growth)."""
    rng = np.random.default_rng(0)
    mu = np.array([2.0, 4.0])
    loop = serve.ServiceLoop(2, config=_steady_cfg(), seed=0)
    _push_rounds(loop, mu, 2, rng)  # warm both cond branches + caches
    gc.collect()
    base = len(jax.live_arrays())
    for _ in range(6):
        _push_rounds(loop, mu, 1, rng)
    gc.collect()
    assert len(jax.live_arrays()) <= base


# -------------------------------------------------------------- checkpointing
def test_serve_state_checkpoints_and_resumes_bitwise(tmp_path):
    from repro.checkpoint.checkpoint import CheckpointManager

    rng = np.random.default_rng(2)
    mu = np.array([3.0, 5.0])
    loop = serve.ServiceLoop(2, config=_steady_cfg(), seed=4)
    _push_rounds(loop, mu, 3, rng)
    # leave telemetry BUFFERED so restore must bring the ring back too
    fr = np.full(2, 0.5, np.float32)
    loop.push(fr, (fr**0.9 * mu).astype(np.float32))

    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, loop.state._asdict(), {"step": 1})
    ckpt.wait()

    template = serve.init(loop.config, 2, jax.random.PRNGKey(4))._asdict()
    restored, _ = ckpt.restore(template)
    state2 = serve.ServeState(**restored)
    assert _leaves_equal(loop.state, state2)

    # both copies tick identically from here
    loop2 = serve.ServiceLoop(2, config=loop.config, state=state2)
    i1, i2 = loop.tick(), loop2.tick()
    assert int(i1.drained) == int(i2.drained) == 1
    assert _leaves_equal(loop.state, loop2.state)


# ------------------------------------------------------------------ the driver
def test_launch_serve_smoke_subprocess():
    """``python -m repro.launch.serve --serve-smoke`` is the shippable proof:
    real model serving rounds fed through the service, at least one propose
    AND at least one drift-gated skip, exit 0."""
    repo = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--serve-smoke"],
        capture_output=True, text=True, timeout=600,
        cwd=repo, env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "serve-smoke OK" in proc.stdout
