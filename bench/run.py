#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output,
and each number the correctness check compared, with its limit, as the last
lines of standard error.  Exits 2, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    common.use_program()
    common.use_compile_cache()
    devices = common.device_check(int(cell["chips"]))

    from bench import harness

    e2e, per_layer = harness.cell_metrics(bench, cell["name"])
    result = harness.run_cell(
        common.config(cell["config"]), common.traffic(cell["traffic"]),
        args.seed, args.seconds, bool(args.trace), T_START, devices,
        per_layer=per_layer, e2e=e2e,
        log=lambda msg: print(msg, file=sys.stderr, flush=True))
    harness.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
