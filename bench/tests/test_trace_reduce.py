"""The trace-to-metric reduction on a small recorded trace."""
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce
from bench.metrics import grid_kernel_ms, grid_kernel_roofline, idle_pct, solve_ms


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000,
              stats=[])


def planes():
    """Two beats: host spans, and device ops with one overlap and two gaps."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.beat", 0, 1000), ev("bench.push", 0, 100),
        ev("bench.tick", 100, 400), ev("bench.beat", 1000, 1000),
        ev("bench.generate", 1000, 300), ev("bench.tick", 1300, 700),
        ev("unrelated", 0, 5000)])])
    ops = [
        ev("%while.7 = (f32[8]) while(f32[8] %a), body=%region_1", 100, 300),
        ev("%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop", 100, 200),
        ev("%posterior_grid_fleet_pallas.8 = (f32[8,1,16]) custom-call(f32[8,1,16] %b), "
           'custom_call_target="tpu_custom_call"', 250, 150),  # overlaps
        ev("%fusion.2 = f32[8] fusion(f32[8] %c), kind=kLoop", 1400, 300),
        ev("%fusion.3 = f32[8] fusion(f32[8] %d)", 3000, 100),  # after the window
    ]
    tpu = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_observe_dag(1)", 100, 300),
                                       ev("jit_propose_dag(2)", 1400, 300)]),
        NS(name="XLA Ops", events=ops),
        NS(name="Async XLA Ops", events=[ev("%copy-start.1 = copy-start()", 0, 2000)])])
    return [host, tpu]


def test_busy_union_window_and_gaps():
    red = trace_reduce.reduce_planes(planes())
    assert red["window_s"] == pytest.approx(2000e-6)
    # busy: [100, 400] and [1400, 1700] us: 600 us of the 2000 us window
    assert red["busy_s"] == pytest.approx(600e-6)
    gaps = dict((round(s * 1e6), n) for n, s in red["idle_gaps"])
    # 0-100 under push; 400-1400 has its middle (900) under beat 1 only;
    # 1700-2000 under the second tick.
    assert gaps == {100: "push", 1000: "beat", 300: "tick"}
    assert "fusion" in red["ops"] and "while" not in red["ops"]
    assert red["ops"]["fusion"] == (pytest.approx(500e-6), 2)  # fusion.3 is out
    assert red["ops"]["posterior_grid_fleet_pallas"][1] == 1
    b = trace_reduce.breakdown(red)
    assert b["device_ops"][0] == ["fusion", pytest.approx(500e-6)]
    assert b["idle_gaps"][0] == ["beat", pytest.approx(1000e-6)]


def test_readers_on_the_trace():
    red = trace_reduce.reduce_planes(planes())
    ctx = dict(trace=red, beats=2, pubs=1,
               kernel=dict(workers=8, grid=16, obs=4, launches=1),
               peak=dict(flops_per_s=1e12, hbm_bytes_per_s=1e9))
    assert idle_pct.read(ctx) == pytest.approx(70.0)
    assert grid_kernel_ms.read(ctx) == pytest.approx(0.075)
    assert solve_ms.read(ctx) == pytest.approx(0.3)
    share = grid_kernel_roofline.read(ctx)
    assert 0 < share <= 100


def test_readers_return_nothing_without_events():
    red = trace_reduce.reduce_planes(planes()[:1] + [NS(name="/device:TPU:0", lines=[])])
    assert red["busy_s"] == 0
    ctx = dict(trace=red, beats=2, pubs=1, kernel=dict(workers=8, grid=16, obs=4, launches=1),
               peak=dict(flops_per_s=1e12, hbm_bytes_per_s=1e9))
    assert idle_pct.read(ctx) is None
    assert grid_kernel_ms.read(ctx) is None
    assert grid_kernel_roofline.read(ctx) is None
    assert solve_ms.read(ctx) is None
