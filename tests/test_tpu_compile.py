"""Compile rehearsal for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, kernels that cannot be partitioned), so the main path's
kernel and the whole service tick are compiled here for a described
``v5e:2x2`` topology and must carry the Mosaic call.  Nothing runs: these
tests say nothing about results or times.  The topology is described inside
a fixture, never at import, so every test worker collects the same tests.
The last test checks that ``chip_smoke.py`` refuses to run without a chip.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.moments import BetaParams
from repro.core.sharding import ShardingConfig
from repro.kernels import ops
from repro.kernels.posterior_grid import posterior_grid_fleet_pallas

KERNEL_OP = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache off in this file.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the library onto its chip path: kernels lower to Mosaic and the
    scheduler's auto policy picks them.  Traces made under the patch are
    dropped afterwards so no later test reuses them."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "use_pallas_default", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding) for s in shapes]


@pytest.mark.parametrize(
    "k,g,n",
    [
        (10_000, 256, 64),  # the served shape: default ServeConfig, K = 10^4
        (3, 64, 4),  # the --serve-smoke grid (launch/serve.py)
        (16, 512, 4096),  # long telemetry
        (1_152, 256, 64),  # the montage benchmark cell, folded: 9 x 128 slots
        (12_583, 256, 64),  # the borg-cell benchmark cell
    ],
)
def test_fleet_kernel_compiles_for_v5e(one_chip, k, g, n):
    fn = functools.partial(posterior_grid_fleet_pallas, interpret=False)
    args = _shapes(one_chip, (g,), (k, n), (k, n), (k, n), *[(k,)] * 8)
    compiled = jax.jit(fn).lower(*args).compile()
    # The benchmark's kernel readers find the kernel by this instruction name.
    assert any(
        line.lstrip().startswith("%posterior_grid_fleet_pallas") and KERNEL_OP in line
        for line in compiled.as_text().splitlines()
    )


def _wrapper_args(sharding, lead, g, n):
    grid, t, f, mask, mu, lam, alpha, beta, pa, pb = _shapes(
        sharding, (g,), lead + (n,), lead + (n,), lead + (n,),
        lead, lead, lead, lead, lead, lead,
    )
    prior = BetaParams(pa, pb)
    return (grid, t, f, mu, lam, alpha, beta, prior, prior, mask)


def test_dag_folded_kernel_compiles_for_v5e(one_chip, mosaic):
    """A 5-stage x 2000-worker DAG folds into one S*K-worker launch."""
    args = _wrapper_args(one_chip, (5, 2000), 256, 64)
    lowered = jax.jit(ops.posterior_grid_fleet).lower(*args)
    compiled = lowered.compile()
    assert KERNEL_OP in compiled.as_text()
    assert lowered.out_info.shape == (5, 2000, 2, 256)


def test_sharded_kernel_compiles_for_four_chips(topo, mosaic):
    """The fleet axis split over a four-chip mesh: one kernel per shard."""
    mesh = Mesh(np.array(topo.devices), ("workers",))
    sharding = ShardingConfig(mesh=mesh)
    k, g, n = 100_000, 256, 64
    fleet = NamedSharding(mesh, P("workers"))
    args = list(_wrapper_args(fleet, (k,), g, n))
    args[0] = jax.ShapeDtypeStruct((g,), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
    fn = functools.partial(ops.posterior_grid_fleet, sharding=sharding)
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL_OP in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 3 * k * n * 4 / 2  # telemetry is split, not copied


def test_service_tick_compiles_for_v5e(one_chip, mosaic):
    """The whole default service tick at K = 10^4 with the kernel in it."""
    from repro import serve
    from repro.serve import service

    config = serve.ServeConfig()
    state = jax.eval_shape(
        lambda key: service.init(config, 10_000, key), jax.random.PRNGKey(0)
    )
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        state,
    )
    compiled = service.tick.lower(state, config).compile()
    assert KERNEL_OP in compiled.as_text()


def test_active_service_tick_compiles_for_v5e(one_chip, mosaic):
    """The active-set tick of the borg-fleet-active benchmark cell: K = 10^5,
    a top-6,250 selection, the kernel on the gathered slab, the surrogate
    sweeps over the fleet, and the scopes that name those stages."""
    from repro import sched, serve
    from repro.serve import service

    config = serve.ServeConfig(
        sched=sched.SchedulerConfig(grid_size=256), active_size=6_250
    )
    state = jax.eval_shape(
        lambda key: service.init(config, 100_000, key), jax.random.PRNGKey(0)
    )
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        state,
    )
    text = service.tick.lower(state, config).compile().as_text()
    assert KERNEL_OP in text
    for scope in ("active_select", "active_slab", "surrogate_sweep", "active_merge"):
        assert scope in text, scope


@pytest.mark.parametrize("with_valid", [False, True])
def test_flush_program_compiles_for_v5e(one_chip, with_valid):
    """The service shell's block write at the borg-fleet-active cell's
    shape: 64 staged rows of K = 10^5 into a 64-slot ring, the ring's
    buffers updated in place."""
    from repro.serve import ring_init, service

    k, cap = 100_000, 64
    ring = jax.eval_shape(lambda: ring_init(cap, k))
    ring = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        ring,
    )
    rows = _shapes(one_chip, *[(cap, k)] * (3 if with_valid else 2))
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    valid = rows[2] if with_valid else None
    compiled = service._push_rows.lower(ring, rows[0], rows[1], n, valid).compile()
    assert "input_output_alias" in compiled.as_text()


def test_chip_smoke_stops_at_the_device_check(monkeypatch, capsys):
    """Without a TPU the smoke run exits non-zero before any phase and
    prints no result line."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: None)
    ran = []
    for phase in ("service_phase", "reference_phase", "trainer_phase",
                  "sharded_phase"):
        monkeypatch.setattr(smoke, phase, lambda *a, p=phase: ran.append(p))
    with pytest.raises(SystemExit) as exit_info:
        smoke.main([])
    assert exit_info.value.code not in (0, None)
    assert ran == []
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "needs a TPU" in err
