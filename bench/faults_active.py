#!/usr/bin/env python3
"""Faults of the active-set path, the control, and the readings they give.

    python3 bench/faults_active.py --workload <cell> --seeds 1,2,3 --fault-seeds 3

Each fault takes a ``setattr(owner, name, value)``, such as
``pytest.MonkeyPatch.setattr``, and plants itself in the program under the
timed path; a run of an active-set cell with it planted has to read
``correct`` false, by the check named in ``CAUGHT_BY``.  Clear JAX's caches
after planting, so that the programs are traced anew.

As a command it does what ``bench/calibrate.py`` does for these plants: for
each seed one JSON line of the numbers a run compares, for the program as it
is (``sound``) and, on the first ``--fault-seeds`` seeds, with each plant;
the last line holds the largest sound reading of each number and the
smallest under each plant.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402

from bench import common, faults  # noqa: E402


def _mask(times, mask):
    return jnp.ones_like(times) if mask is None else \
        jnp.broadcast_to(mask, times.shape).astype(times.dtype)


def control(setattr) -> None:
    """The reference active drain in the program's place, the grid path's
    posterior grid in bfloat16: one precision below the float32 the
    configuration states.  The surrogate evaluates no grid."""
    from bench.reference import active as ref_active
    from bench.reference import gibbs as ref_gibbs

    def advance(fleet, times, fracs, config, mask=None, active_idx=None):
        m = _mask(times, mask)
        s = faults._leaves(fleet)
        out = ref_active.surrogate(s, times, fracs, m, n_iters=config.n_iters,
                                   rho=config.discount)
        take = lambda x: x[active_idx]
        slab = ref_gibbs.drain({k: take(v) for k, v in s.items()}, take(times),
                               take(fracs), take(m), n_iters=config.n_iters,
                               grid_size=config.grid_size, rho=config.discount,
                               grid_dtype=jnp.bfloat16)
        out = {k: v.at[active_idx].set(slab[k]) for k, v in out.items()}
        return faults._state(fleet, out), jnp.zeros(times.shape[:-1], times.dtype)

    faults._advance(setattr, advance)


def shifted_active_set(setattr) -> None:
    """The active set moved on by one rotation: the M workers after those
    the priority picks."""
    from repro.serve import service

    real = service.select_active

    def select(m, **kw):
        idx, pri = real(m, **kw)
        return (idx + m) % pri.shape[0], pri

    setattr(service, "select_active", select)


def surrogate_priors_discounted(setattr) -> None:
    """Power-prior forgetting applied to every worker's Beta priors, the
    surrogate's too, where only the grid path's are discounted."""
    from repro.core import gibbs
    from repro.kernels.ops import use_pallas_default

    def advance(fleet, times, fracs, config, mask=None, active_idx=None):
        use_pallas = config.use_pallas
        return gibbs.gibbs_batch(
            gibbs.discount_state(fleet, config.discount), times, fracs, mask,
            n_iters=config.n_iters, grid_size=config.grid_size,
            use_pallas=use_pallas_default() if use_pallas is None else use_pallas,
            active_idx=active_idx)

    faults._advance(setattr, advance)


FAULTS = {"shifted_active_set": shifted_active_set,
          "surrogate_priors_discounted": surrogate_priors_discounted,
          "half_batch": faults.half_batch}
CAUGHT_BY = {"control": "posterior_gap",
             "shifted_active_set": "active_set_mismatch",
             "surrogate_priors_discounted": "posterior_gap",
             "half_batch": "posterior_gap"}


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
    from bench.calibrate import planted, readings

    plants = dict(control=control, **FAULTS)
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
