"""The span and counter registry (repro.obs): records, bounds, JAX's events.

The registry is process-wide, so each test works on the records it made
itself (found by name, or after ``obs.reset()``) and on differences of the
trace tallies, never on their absolute values.
"""
import gc
import glob
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import obs


@pytest.fixture
def fresh():
    obs.reset()
    yield
    obs.reset()


def _named(name):
    return [s for s in obs.snapshot()["spans"] if s["name"] == name]


def test_spans_nest_and_name_their_parents(fresh):
    with obs.span("t.outer", beat=5, kind="x") as outer:
        with obs.span("t.mid"):
            with obs.span("t.inner", beat=9):
                pass
        outer.set(rows=3)
    with obs.span("t.alone"):
        pass
    (o,), (m,), (i,), (a,) = (_named(n) for n in
                              ("t.outer", "t.mid", "t.inner", "t.alone"))
    assert o["parent"] is None and a["parent"] is None
    assert m["parent"] == o["id"] and i["parent"] == m["id"]
    # a span given no beat takes its parent's; one given a beat keeps it
    assert (o["beat"], m["beat"], i["beat"], a["beat"]) == (5, 5, 9, None)
    assert o["attrs"] == {"kind": "x", "rows": 3}
    # the children lie inside the parent on the same clock
    assert o["start_ns"] <= m["start_ns"] <= i["start_ns"]
    assert i["start_ns"] + i["duration_ns"] <= o["start_ns"] + o["duration_ns"]
    # records are closed in order: innermost first
    names = [s["name"] for s in obs.snapshot()["spans"]]
    assert names == ["t.inner", "t.mid", "t.outer", "t.alone"]


def test_aggregates_count_total_and_max(fresh):
    for _ in range(5):
        with obs.span("t.agg"):
            pass
    durations = [s["duration_ns"] * 1e-6 for s in _named("t.agg")]
    agg = obs.snapshot()["aggregates"]["t.agg"]
    assert agg["count"] == 5
    assert agg["total_ms"] == pytest.approx(sum(durations))
    assert agg["max_ms"] == pytest.approx(max(durations))


def test_counters_add_and_reset():
    obs.reset()
    obs.count("t.rows", 4)
    obs.count("t.rows")
    obs.count("t.other")
    assert obs.snapshot()["counters"] == {"t.rows": 5, "t.other": 1}
    obs.reset()
    assert obs.snapshot()["counters"] == {}


def test_log_keeps_the_newest_records_and_aggregates_keep_all(fresh):
    n = obs.LOG_SIZE + 100
    for i in range(n):
        with obs.span("t.many", beat=i):
            pass
    spans = obs.snapshot()["spans"]
    assert len(spans) == obs.LOG_SIZE
    assert spans[0]["beat"] == 100 and spans[-1]["beat"] == n - 1
    assert obs.snapshot()["aggregates"]["t.many"]["count"] == n


def test_trace_counter_rises_on_a_retrace_not_on_a_cache_hit(fresh):
    def obs_probe_fn(x):
        return x * 2.0 + 1.0

    f = jax.jit(obs_probe_fn)
    with obs.span("t.first") as first:
        f(jnp.ones(3)).block_until_ready()
    with obs.span("t.hit"):
        f(jnp.ones(3)).block_until_ready()
    with obs.span("t.retrace"):
        f(jnp.ones(4)).block_until_ready()  # a new shape traces again
    (a,), (b,), (c,) = (_named(n) for n in ("t.first", "t.hit", "t.retrace"))
    assert a["traces"] >= 1 and a["compiles"] >= 1
    assert b["traces"] == 0 and b["compiles"] == 0
    assert c["traces"] >= 1 and c["compiles"] >= 1
    assert first.id == a["id"]
    assert obs.snapshot()["traces"]["obs_probe_fn"] == 2


def test_garbage_collection_inside_a_span_is_timed(fresh):
    with obs.span("t.gc"):
        gc.collect()
    with obs.span("t.nogc"):
        pass
    (a,), (b,) = _named("t.gc"), _named("t.nogc")
    assert 0.0 < a["gc_ms"] <= a["duration_ns"] * 1e-6
    assert b["gc_ms"] == 0.0


def _run_python(code):
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env={**os.environ, "PYTHONPATH": src,
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr


def test_hook_counts_the_first_trace_of_a_program_without_spans():
    """``hook()`` starts the tallies once; ``init_dag`` calls it, so the DAG
    programs, which open no span, are counted from their first trace."""
    _run_python(
        "import gc, jax, jax.numpy as jnp\n"
        "from repro import obs, sched\n"
        "assert not obs._hooked\n"
        "dag = sched.WorkflowDAG(preds=((), (0,)), num_workers=2)\n"
        "sched.init_dag(sched.SchedulerConfig(), dag, jax.random.PRNGKey(0))\n"
        "assert obs._hooked\n"
        "obs.hook()\n"
        "assert gc.callbacks.count(obs._on_gc) == 1\n"
        "def obs_scoped_fn(x):\n"
        "    with jax.named_scope('t_stage'):\n"
        "        return jnp.sin(x) * 3.0\n"
        "text = jax.jit(obs_scoped_fn).lower(jnp.ones(3)).as_text(debug_info=True)\n"
        "assert 't_stage' in text\n"
        "assert obs.snapshot()['traces']['obs_scoped_fn'] == 1\n"
    )


def test_profiler_trace_holds_the_span_on_the_host_plane(fresh, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with obs.span("t.profiled", beat=1):
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    hosts = [
        (plane.name, ev.name)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(obs.PREFIX)
    ]
    assert ("/host:CPU", "repro.t.profiled") in hosts
    assert len(_named("t.profiled")) == 1  # the log records it as well


def test_threads_record_at_once(fresh):
    """Threads share the log, the aggregates and the counters: none of their
    updates is lost, and each thread's spans nest on its own stack."""
    n, k = 500, 8
    start = threading.Barrier(k)

    def work(tag):
        start.wait()
        for i in range(n):
            with obs.span(f"t.thread{tag}", beat=i):
                with obs.span("t.child"):
                    obs.count("t.rows")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(k)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = obs.snapshot()
    spans = snap["spans"]
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans) == 2 * n * k  # ids are unique
    assert snap["aggregates"]["t.child"]["count"] == n * k
    assert snap["counters"]["t.rows"] == n * k
    for t in range(k):
        parents = {s["id"]: s for s in spans if s["name"] == f"t.thread{t}"}
        children = [s for s in spans if s["parent"] in parents]
        assert len(parents) == len(children) == n
        # a child takes the beat of the parent on its own thread's stack
        assert all(c["beat"] == parents[c["parent"]]["beat"] for c in children)


def test_importing_repro_hooks_nothing():
    code = (
        "import gc, jax\n"
        "import repro, repro.serve, repro.sched, repro.obs\n"
        "assert not repro.obs._hooked\n"
        "assert repro.obs._on_gc not in gc.callbacks\n"
        "jax.jit(lambda x: x + 1)(1.0)\n"
        "assert repro.obs.snapshot()['traces'] == {}\n"
        "with repro.obs.span('t'):\n"
        "    pass\n"
        "assert repro.obs._on_gc in gc.callbacks\n"
        "jax.jit(lambda x: x + 2)(1.0)\n"
        "assert repro.obs.snapshot()['traces']['<lambda>'] == 1\n"
    )
    _run_python(code)
