"""The readers of the program's span registry, on hand-made snapshots."""
import sys

import pytest

from bench.metrics import push_span_ms, retraces, tick_solve_ms, tick_wait_ms

READERS = (push_span_ms, tick_wait_ms, tick_solve_ms, retraces)


def rec(name, beat, ms, traces=0, compiles=0, **attrs):
    return dict(id=0, parent=None, name=name, beat=beat, start_ns=0,
                duration_ns=int(ms * 1e6), traces=traces, compiles=compiles,
                gc_ms=0.0, attrs=attrs)


def empty():
    return dict(spans=[], aggregates={}, counters={}, traces={}, compiles={})


def beat(b, wait_ms, proposed, push_ms=1.0, traces=0):
    return [rec("serve.push", b, push_ms, traces=traces),
            rec("serve.tick", b, wait_ms + 2.0, traces=traces,
                proposed=proposed, fired=False, drained=1),
            rec("serve.wait", b, wait_ms)]


@pytest.fixture
def registry(monkeypatch):
    from repro import obs

    snap = empty()
    monkeypatch.setattr(obs, "snapshot", lambda: snap)
    return snap


def test_readers_on_a_hand_made_borg_snapshot(registry):
    # beat 0 traced and compiled (warm-up): left out of every span reader
    registry["spans"] += beat(0, 900.0, True, push_ms=50.0, traces=3)
    registry["spans"] += beat(1, 400.0, False)
    registry["spans"] += beat(2, 410.0, False, push_ms=0.5)
    registry["spans"] += beat(3, 460.0, True)
    registry["traces"].update(push=1, tick=1, solve_published=1, add=40)
    assert push_span_ms.read({}) == pytest.approx((1.0 + 0.5 + 1.0) / 3)
    assert tick_wait_ms.read({}) == pytest.approx(405.0)
    ctx = {}
    assert tick_solve_ms.read(ctx) == pytest.approx(55.0)
    assert ctx["notes"] == ["tick_solve_ms over 1 proposing and 2 holding ticks"]
    assert retraces.read({}) == 0


def test_retraces_counts_traces_beyond_the_first_of_each_entry_point(registry):
    registry["traces"].update(observe_dag=3, propose_dag=1, multiply=90)
    assert retraces.read({}) == 2
    assert push_span_ms.read({}) is None  # no push span in a DAG run
    assert tick_wait_ms.read({}) is None and tick_solve_ms.read({}) is None


def test_solve_needs_both_kinds_of_tick(registry):
    registry["spans"] += beat(1, 400.0, False) + beat(2, 401.0, False)
    assert tick_wait_ms.read({}) == pytest.approx(400.5)
    assert tick_solve_ms.read({}) is None


def test_every_reader_gives_none_on_an_empty_registry(registry):
    assert [r.read({}) for r in READERS] == [None] * len(READERS)


def test_every_reader_gives_none_without_the_registry(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    assert [r.read({}) for r in READERS] == [None] * len(READERS)
