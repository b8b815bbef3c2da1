#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --fault-seeds 3

For each seed it builds the cell at its own size, beats it through the drain
that the check compares and on to a publication after it, and prints one
JSON line of the numbers the run compares: ``sound`` for the program as it
is, and, on the first ``--fault-seeds`` seeds, the same numbers with the
control and each fault of ``bench/faults.py`` planted in the program; and
beside them ``makespan_ratio`` over the publications the short run made.

The last line holds the largest sound reading of each number, and the
smallest reading of each number under the control and under each fault.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def readings(cfg, traffic, seed) -> dict:
    """The numbers one run of the cell compares, from a short run."""
    from bench import harness
    from bench.generator import sub_seeds

    drv = importlib.import_module(f"bench.drivers.{cfg['driver']}").Driver(
        cfg, traffic, sub_seeds(seed, harness.SEED_NAMES), False)
    for _ in range(int(cfg["warm_beats"])):
        drv.beat("warm", -1)
    index = 0
    while index <= drv.snap_beat or not drv.pubs:
        drv.beat("window", index)
        index += 1
    drv.collect()
    return dict(drv.checks(), makespan_ratio=drv.makespan_ratio())


def planted(cfg, traffic, seed, plant) -> dict:
    """``readings`` with ``plant`` put in the program."""
    import jax
    import pytest

    with pytest.MonkeyPatch.context() as mp:
        plant(mp.setattr)
        jax.clear_caches()
        try:
            return readings(cfg, traffic, seed)
        finally:
            jax.clear_caches()


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    bench = common.benchmark()
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    common.use_program()
    common.use_compile_cache()
    common.device_check(int(cell["chips"]))
    cfg, traffic = common.config(cell["config"]), common.traffic(cell["traffic"])
    from bench import faults

    plants = dict(control=faults.control, **faults.FAULTS, **faults.QUALITY)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row = {"seed": seed, "sound": readings(cfg, traffic, seed)}
        if i < args.fault_seeds:
            for name, plant in plants.items():
                row[name] = planted(cfg, traffic, seed, plant)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "seconds": time.perf_counter() - t0}
    for part in ("sound",) + tuple(plants):
        got = [r[part] for r in rows if part in r]
        for k in got[0] if got else ():
            agg = max if part == "sound" else min
            summary[f"{part}.{k}.{agg.__name__}"] = agg(g[k] for g in got)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
