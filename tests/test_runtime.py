"""Training runtime: checkpointing, data pipeline, compression, sharding rules."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.data.pipeline import DataIterator
from repro.distributed.compression import init_error_feedback, make_compressor


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3))}}
    mgr.save(5, tree, {"step": 5, "note": "x"})
    restored, extra = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(10.0))
    assert extra["step"] == 5


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = {"w": jnp.zeros(4)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, {"step": s})
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_ignores_partial_tmp(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_write=False)
    tree = {"w": jnp.zeros(4)}
    mgr.save(1, tree, {"step": 1})
    # simulate a crash mid-write
    bad = tmp_path / "step_00000002.tmp"
    bad.mkdir()
    (bad / "arr_00000.npy").write_bytes(b"garbage")
    mgr2 = CheckpointManager(str(tmp_path), keep=3, async_write=False)
    assert mgr2.latest_step() == 1
    assert not bad.exists()  # purged


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=1, async_write=True)
    tree = {"w": jnp.full((8,), 7.0)}
    mgr.save(3, tree, {"step": 3})
    mgr.wait()
    restored, _ = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full(8, 7.0))


def test_checkpoint_manifest_records_keypaths(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = {"a": jnp.arange(4.0), "b": {"c": jnp.ones(2)}}
    mgr.save(1, tree, {"step": 1})
    manifest = json.loads(
        (tmp_path / "step_00000001" / "manifest.json").read_text()
    )
    assert manifest["keypaths"] == ["['a']", "['b']['c']"]


def test_restore_by_name_subset_on_shape_drift(tmp_path):
    """A drifted leaf keeps its template value; matching leaves restore by
    name even though positional order shifted — and the report says which."""
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    saved = {
        "params": {"w": jnp.full((3,), 7.0)},
        "sched": {"ewma_count": jnp.zeros((), jnp.int32)},  # legacy scalar
    }
    mgr.save(1, saved, {"step": 1})
    template = {
        "params": {"w": jnp.zeros((3,))},
        "sched": {"ewma_count": jnp.ones((2,), jnp.int32)},  # now per-worker
    }
    tree, extra, report = mgr.restore_by_name(template)
    np.testing.assert_array_equal(np.asarray(tree["params"]["w"]), np.full(3, 7.0))
    np.testing.assert_array_equal(  # template kept, not the drifted scalar
        np.asarray(tree["sched"]["ewma_count"]), np.ones(2)
    )
    assert report["restored"] == ["['params']['w']"]
    assert report["skipped"] == ["['sched']['ewma_count']"]
    assert extra["step"] == 1
    # positional restore must refuse the same checkpoint (shape mismatch)
    with pytest.raises(ValueError):
        mgr.restore(template)


def test_restore_by_name_rejects_dtype_drift_and_prekeypath(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    mgr.save(1, {"x": jnp.arange(4, dtype=jnp.int32)}, {"step": 1})
    tree, _, report = mgr.restore_by_name({"x": jnp.zeros(4, jnp.float32)})
    assert report["skipped"] == ["['x']"]  # same shape, wrong dtype
    np.testing.assert_array_equal(np.asarray(tree["x"]), np.zeros(4))
    # pre-keypath checkpoints are explicit: positional restore only
    mpath = tmp_path / "step_00000001" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    del manifest["keypaths"]
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="predates key-path"):
        mgr.restore_by_name({"x": jnp.zeros(4, jnp.int32)})


def test_data_iterator_deterministic_and_resumable():
    it1 = DataIterator(vocab_size=100, seq_len=16, global_batch=8,
                       num_microbatches=2, seed=3)
    b1 = next(it1)
    state = it1.state_dict()
    b2 = next(it1)

    it2 = DataIterator(vocab_size=100, seq_len=16, global_batch=8,
                       num_microbatches=2, seed=3)
    next(it2)
    it2.load_state_dict(json.loads(json.dumps(state)))  # survives JSON
    b2b = next(it2)
    np.testing.assert_array_equal(b2["tokens"], b2b["tokens"])
    assert b1["tokens"].shape == (2, 4, 16)
    assert (b1["tokens"] >= 0).all() and (b1["tokens"] < 100).all()
    assert not np.array_equal(b1["tokens"], b2["tokens"])


def test_data_iterator_shards_disjoint():
    a = DataIterator(vocab_size=50, seq_len=8, global_batch=8,
                     num_microbatches=2, seed=1, shard_index=0, shard_count=2)
    b = DataIterator(vocab_size=50, seq_len=8, global_batch=8,
                     num_microbatches=2, seed=1, shard_index=1, shard_count=2)
    ba, bb = next(a), next(b)
    assert ba["tokens"].shape == (2, 2, 8)
    assert not np.array_equal(ba["tokens"], bb["tokens"])


@pytest.mark.parametrize("kind", ["int8_ef", "topk_ef"])
def test_compression_error_feedback(kind):
    grads = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)),
                              jnp.float32)}
    compress, init_ef = make_compressor(kind, None, ratio=0.05)
    ef = init_ef(grads)
    sent, ef2 = compress(grads, ef)
    # EF invariant: sent + residual == original (+ old residual)
    np.testing.assert_allclose(
        np.asarray(sent["w"] + ef2["w"]), np.asarray(grads["w"]),
        rtol=1e-5, atol=1e-5,
    )
    if kind == "topk_ef":
        nz = float(jnp.mean((sent["w"] != 0).astype(jnp.float32)))
        assert nz <= 0.08  # ~5% density requested


def test_sharding_rules_divisibility_fallback():
    import os
    from repro.distributed.sharding import default_rules, spec_for
    # build a small host mesh without touching device count: reuse real device
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])

    # fake a 16x16 mesh via a stub object exposing shape/axis_names
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = default_rules(FakeMesh())
    # divisible: vocab 64000 -> model; embed 4096 -> data
    spec = spec_for((64000, 4096), ("vocab", "embed"), FakeMesh(), rules)
    assert spec[0] == "model" and spec[1] == "data"
    # 9 heads not divisible by 16 -> replicated
    spec = spec_for((576, 9, 64), ("embed", "heads", "head_dim"), FakeMesh(), rules)
    assert spec[1] is None and spec[2] is None
    # experts 40 not divisible -> replicated, mlp 512 -> model
    spec = spec_for((40, 1536, 512), ("experts", "embed", "mlp"), FakeMesh(), rules)
    assert spec[0] is None and spec[2] == "model"
    # experts 128 divisible by data -> data
    spec = spec_for((128, 7168, 4864), ("experts", "embed", "mlp"), FakeMesh(), rules)
    assert spec[0] == "data" and spec[2] == "model"


def test_cache_rules_prefer_kv_heads_then_seq():
    from repro.distributed.sharding import cache_rules, spec_for

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = cache_rules(FakeMesh())
    # kvh=16 divisible -> kv_heads claims model, seq untouched
    spec = spec_for((128, 32768, 16, 64), ("batch", "seq", "kv_heads", "head_dim"),
                    FakeMesh(), rules)
    assert spec[2] == "model" and spec[1] is None
    # kvh=4 not divisible -> seq claims model (flash-decode sharding)
    spec = spec_for((128, 32768, 4, 64), ("batch", "seq", "kv_heads", "head_dim"),
                    FakeMesh(), rules)
    assert spec[1] == "model" and spec[2] is None


def _record_cache_config(monkeypatch):
    """Stand in for ``jax.config`` inside the cache helper: the tests never
    turn the persistent cache on, they only see what the helper would set."""
    import types

    from repro.launch import cache

    calls = []
    stub = types.SimpleNamespace(
        config=types.SimpleNamespace(update=lambda *a: calls.append(a))
    )
    monkeypatch.setattr(cache, "jax", stub)
    return cache, calls


def test_compile_cache_left_to_jax_when_env_names_it(monkeypatch, tmp_path):
    cache, calls = _record_cache_config(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    import pathlib

    cache, calls = _record_cache_config(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert cache.enable_compile_cache() == want
    assert cache.enable_compile_cache() == want  # fixed: never per-process
    assert calls == [("jax_compilation_cache_dir", want)] * 2
