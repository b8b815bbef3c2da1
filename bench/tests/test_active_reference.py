"""The active-set cell's check, its faults, and its metric readers.

The check (``bench/drivers/serve_active.py``) runs on a short run of the
``borg-fleet-active`` configuration cut to K = 64, M = 8, the compared drain
after the first full rotation; each fault of ``bench/faults_active.py`` is
planted in the program and has to read past its limit.  The readers read a
hand-made registry snapshot and trace reduction.
"""
import numpy as np
import pytest

from bench import common, faults_active
from bench.calibrate import planted, readings
from bench.drivers.serve_active import CHUNKS, AheadFleet, active_set_mismatch
from bench.generator import FLOOR, sub_seeds
from bench.metrics import active_tick_wait_ms, slab_kernel_ms, slab_kernel_roofline
from bench.workcount import grid_posterior_work

SEED = 2**31 + 7
K, M = 64, 8


def tiny():
    cfg = common.config("borg-fleet-active")
    cfg["workers"] = K
    cfg["serve"]["active_size"] = M
    cfg["check"]["sampled_workers"] = K
    cfg["check"]["snapshot_beat_range"] = [K // M, K // M + 1]
    return cfg, common.traffic("steady")


def over(checks, limits):
    return {k for k, v in checks.items() if k in limits and not v <= limits[k]}


def test_sound_run_is_within_every_limit():
    cfg, tr = tiny()
    got = readings(cfg, tr, SEED)
    assert over(got, cfg["limits"]) == set(), got
    assert got["active_set_mismatch"] == 0
    assert got["refresh_age_max"] == K // M - 1


@pytest.mark.parametrize("fault", ["control", *faults_active.FAULTS])
def test_fault_reads_past_its_limit(fault):
    cfg, tr = tiny()
    plant = faults_active.control if fault == "control" else faults_active.FAULTS[fault]
    got = planted(cfg, tr, SEED, plant)
    assert faults_active.CAUGHT_BY[fault] in over(got, cfg["limits"]), got


def test_rows_made_ahead_follow_the_fleets_law_from_the_seeds_streams():
    """Each block of rows from its own stream of the noise seed, in beat
    order; made ahead for the split of the beat before, and made again from
    the same draws where a publication changed the split."""
    cfg, tr = tiny()
    seeds = sub_seeds(SEED, ("layout", "noise"))
    ahead = AheadFleet(cfg, tr, seeds)
    streams = [np.random.default_rng(s)
               for s in np.random.SeedSequence(seeds["noise"]).spawn(CHUNKS)]
    n = 12  # blocks of 2 and of 1 row
    rng = np.random.default_rng(0)
    fr = np.full(K, 1.0 / K, np.float32)
    try:
        for beat in range(6):
            if beat in (2, 3, 5):
                fr = rng.dirichlet(np.ones(K)).astype(np.float32)
            got = ahead.rows(fr, n)
            z = np.concatenate([s.standard_normal((len(b), K)) for s, b in
                                zip(streams, np.array_split(np.arange(n), CHUNKS))])
            f = np.maximum(fr.astype(np.float64), FLOOR)
            want = np.maximum(f**ahead.alpha * ahead.mu + f**ahead.beta * ahead.sigma * z,
                              FLOOR).astype(np.float32)
            assert got.dtype == np.float32
            np.testing.assert_array_equal(got, want)
    finally:
        ahead.close()


def test_mismatch_leaves_out_workers_tied_at_the_boundary():
    age = np.array([5, 3, 3, 3, 0], np.int32)
    flat = dict(nu=np.full(5, 10.0, np.float32), anomaly=np.zeros(5, np.float32),
                live=np.ones(5, np.float32))
    # M = 2: worker 0, then one of the three tied at age 3
    assert active_set_mismatch(2, age, after=np.array([0, 1, 1, 0, 1]), **flat) == 0
    assert active_set_mismatch(2, age, after=np.array([1, 0, 1, 1, 0]), **flat) == 2
    # no tie across the boundary: every worker counts
    assert active_set_mismatch(1, age, after=np.array([1, 0, 1, 1, 1]), **flat) == 2
    # a dead worker is never in the reference's set
    dead = dict(flat, live=np.array([0, 1, 1, 1, 1], np.float32))
    age = np.array([5, 3, 2, 1, 0], np.int32)
    assert active_set_mismatch(1, age, after=np.array([0, 1, 1, 1, 1]), **dead) == 2
    assert active_set_mismatch(1, age, after=np.array([1, 0, 1, 1, 1]), **dead) == 0


# -- the readers ---------------------------------------------------------------
def rec(name, beat, ms, **attrs):
    return dict(id=0, parent=None, name=name, beat=beat, start_ns=0,
                duration_ns=int(ms * 1e6), traces=0, compiles=0, gc_ms=0.0,
                attrs=attrs)


def tick(beat, wait_ms, proposed, **attrs):
    return [rec("serve.tick", beat, wait_ms + 2.0, proposed=proposed,
                fired=False, drained=64, **attrs),
            rec("serve.wait", beat, wait_ms)]


@pytest.fixture
def registry(monkeypatch):
    from repro import obs

    snap = dict(spans=[], aggregates={}, counters={}, traces={}, compiles={})
    monkeypatch.setattr(obs, "snapshot", lambda: snap)
    return snap


KERNEL = dict(workers=6250, grid=256, obs=64, launches=20, fleet=100_000)


def test_active_tick_wait_reads_holding_active_ticks(registry):
    registry["spans"] += tick(1, 40.0, False, refreshed=6250, oldest=15)
    registry["spans"] += tick(2, 44.0, False, refreshed=6250, oldest=15)
    registry["spans"] += tick(3, 500.0, True, refreshed=6250, oldest=15)  # solve
    registry["spans"] += tick(4, 200.0, False, refreshed=100_000, oldest=0)  # dense
    registry["spans"] += tick(5, 1.0, False, refreshed=0, oldest=15)  # empty
    assert active_tick_wait_ms.read({"kernel": KERNEL}) == pytest.approx(42.0)


def test_active_tick_wait_is_none_where_ticks_carry_no_refreshed(registry):
    registry["spans"] += tick(1, 40.0, False) + tick(2, 41.0, False)
    assert active_tick_wait_ms.read({"kernel": KERNEL}) is None
    assert active_tick_wait_ms.read({}) is None


def test_slab_kernel_readers_price_m_workers():
    events = [("%posterior_grid_fleet_pallas.3 = tpu_custom_call(...)", 0, 4.0e-4)] * 40
    events.append(("%fusion.1 = fusion(...)", 0, 1.0))
    ctx = dict(trace={"events": events}, beats=2, kernel=KERNEL,
               peak=common.peaks()["devices"]["TPU v5 lite"])
    assert slab_kernel_ms.read(ctx) == pytest.approx(8.0)
    ops, nbytes = grid_posterior_work(6250, 256, 64)
    least = max(ops / 1.97e14, nbytes / 8.19e11) * 40
    assert slab_kernel_roofline.read(ctx) == pytest.approx(100.0 * least / 0.016)
    assert ctx["notes"] == ["grid_kernel_roofline is memory-bound"]
    assert slab_kernel_ms.read(dict(ctx, trace={"events": events[-1:]})) is None
