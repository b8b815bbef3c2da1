"""Benchmark harness: one module per paper table/figure + system benches.

Emits ``name,us_per_call,derived`` CSV rows.  ``python -m benchmarks.run``;
``--smoke`` runs the fast CI subset (frontier sweep + partitioner quality +
the fleet-scale estimation-engine cases) so a CPU-only runner finishes in
minutes; ``--json PATH`` additionally persists every emitted row (plus the
suite name and failures) as a JSON artifact — CI uploads the smoke run as
``BENCH_<pr>.json`` so the perf trajectory accumulates across PRs.

The artifact schema, the interleaved min-time A/B methodology behind the
``*_ref`` / ``*_fused`` / ``*_sharded`` row families, and the exact
regeneration commands are documented in ``docs/benchmarks.md``.
"""
from __future__ import annotations

import json
import platform
import sys
import traceback

from benchmarks import (
    bench_dag,
    bench_fleet_scale,
    bench_frontier,
    bench_gibbs_convergence,
    bench_hier,
    bench_kernels,
    bench_partitioner,
    bench_posterior_approx,
    bench_serve,
    bench_train_step,
    common,
)
from repro.launch.cache import enable_compile_cache

ALL = [
    ("fig1_2_frontier", bench_frontier.main),
    ("fig3_4_posterior_approx", bench_posterior_approx.main),
    ("fig5_gibbs_convergence", bench_gibbs_convergence.main),
    ("partitioner_vs_naive", bench_partitioner.main),
    ("kernels", bench_kernels.main),
    ("dag_engine", bench_dag.main),
    ("train_step", bench_train_step.main),
    ("serve_loop", bench_serve.main),
    ("hier_pooling", bench_hier.main),
    ("fleet_scale", bench_fleet_scale.main),
]

SMOKE = [
    ("fig1_2_frontier", bench_frontier.main),
    ("partitioner_vs_naive", bench_partitioner.main),
    ("kernels_fleet", bench_kernels.fleet_main),
    ("gibbs_fleet_engine", bench_gibbs_convergence.fleet_main),
    ("dag_stacked_engine", bench_dag.smoke_main),
    ("serve_loop", bench_serve.main),
    ("hier_pooling", bench_hier.main),
    ("fleet_scale", bench_fleet_scale.smoke_main),
]


def main(argv=None) -> None:
    enable_compile_cache()
    argv = sys.argv[1:] if argv is None else argv
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            sys.exit("usage: python -m benchmarks.run [--smoke] [--json PATH]")
        json_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    unknown = [a for a in argv if a != "--smoke"]
    if unknown:
        sys.exit(f"usage: python -m benchmarks.run [--smoke] [--json PATH]  (got {unknown})")
    suite = SMOKE if "--smoke" in argv else ALL
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suite:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            print(f"{name},FAILED,{type(e).__name__}: {e}")
    if json_path:
        import jax

        payload = {
            "suite": "smoke" if "--smoke" in argv else "all",
            "backend": jax.default_backend(),
            # Cross-PR comparisons must match device_count: forcing N host
            # devices (the CI mesh recipe) partitions the machine, which
            # shifts even the single-device rows (docs/benchmarks.md).
            "device_count": jax.device_count(),
            "platform": platform.platform(),
            "failed": failed,
            "rows": common.ROWS,
        }
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {len(common.ROWS)} rows to {json_path}")
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
