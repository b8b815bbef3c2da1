"""The comparison that decides ``correct``: its control and its faults.

Each test drives a whole run of a cell through the harness, at a size the
CPU holds and without the harness's look for a chip, and reads ``correct``.
The control and the faults (``bench/faults.py``) are planted in the program
underneath the timed path.
"""
import time

import jax
import pytest

from bench import common, faults, harness

CELLS = ("borg-cell.steady", "montage.steady")


def tiny(cell_name):
    bench = common.benchmark()
    cell = {c["name"]: c for c in bench["workloads"]}[cell_name]
    cfg = common.config(cell["config"])
    if "workers" in cfg:
        cfg["workers"] = 64
        cfg["check"]["sampled_workers"] = 64
    else:
        cfg["workers_per_stage"] = 8
        cfg["check"]["sampled_workers"] = 72
        cfg["makespan_mc_samples"] = 256
    cfg["check"]["snapshot_beat_range"] = [1, 2]
    return cell, cfg, common.traffic(cell["traffic"])


def run(cell_name, seed=2**31 + 7, seconds=0.5):
    _, cfg, tr = tiny(cell_name)
    jax.clear_caches()
    try:
        return harness.run_cell(cfg, tr, seed, seconds, False,
                                time.perf_counter(), jax.devices()[:1],
                                log=lambda m: None)
    finally:
        jax.clear_caches()


def failed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] is None or not c["value"] <= c["limit"]}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


# -- the control and the faults, planted in the program ---------------------
def planted(cell, name, monkeypatch):
    plant = faults.control if name == "control" else faults.FAULTS[name]
    plant(monkeypatch.setattr)
    res = run(cell)
    assert not res["correct"]
    assert faults.CAUGHT_BY[name] in failed(res), res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell, monkeypatch):
    planted(cell, "control", monkeypatch)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered_answer"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    planted(cell, fault, monkeypatch)

