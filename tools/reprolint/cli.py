"""``python -m tools.reprolint`` — the command-line entry point.

Usage::

    python -m tools.reprolint src tests
    python -m tools.reprolint src --format=json
    python -m tools.reprolint src --baseline tools/reprolint/baseline.json
    python -m tools.reprolint --write-baseline tools/reprolint/baseline.json src
    python -m tools.reprolint --check-layer-docs    # architecture.md in sync?
    python -m tools.reprolint --sync-layer-docs     # rewrite the doc section

Exit codes: 0 clean, 1 findings (or doc drift), 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

from .engine import Linter, apply_baseline, load_baseline, write_baseline
from .layers import LayerMap
from .rules import all_rules

DEFAULT_DOC = Path("docs/architecture.md")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m tools.reprolint",
        description="AST-based JAX/Pallas invariant checker (rules RL001-RL007)",
    )
    parser.add_argument("paths", nargs="*", help="files or directories to lint")
    parser.add_argument(
        "--format", choices=("text", "json"), default="text", dest="fmt"
    )
    parser.add_argument(
        "--baseline",
        type=Path,
        default=None,
        help="JSON baseline of accepted findings (filtered out of the report)",
    )
    parser.add_argument(
        "--write-baseline",
        type=Path,
        default=None,
        help="write the current findings as a new baseline and exit 0",
    )
    parser.add_argument(
        "--rules",
        default=None,
        help="comma-separated subset of rule ids to run (default: all)",
    )
    parser.add_argument(
        "--check-layer-docs",
        action="store_true",
        help="verify docs/architecture.md matches layers.toml",
    )
    parser.add_argument(
        "--sync-layer-docs",
        action="store_true",
        help="rewrite the generated layer-map section of docs/architecture.md",
    )
    parser.add_argument(
        "--layer-doc", type=Path, default=DEFAULT_DOC, help=argparse.SUPPRESS
    )
    return parser


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.check_layer_docs or args.sync_layer_docs:
        layer_map = LayerMap.load()
        in_sync = layer_map.sync_doc(args.layer_doc, write=args.sync_layer_docs)
        if args.sync_layer_docs:
            print(f"{args.layer_doc}: layer-map section synced")
        elif in_sync:
            print(f"{args.layer_doc}: layer-map section in sync with layers.toml")
        else:
            print(
                f"{args.layer_doc}: layer-map section is STALE — run "
                "`python -m tools.reprolint --sync-layer-docs`",
                file=sys.stderr,
            )
            return 1
        if not args.paths:
            return 0

    if not args.paths:
        print("error: no paths given", file=sys.stderr)
        return 2

    rules = all_rules()
    if args.rules:
        wanted = {r.strip().upper() for r in args.rules.split(",")}
        unknown = wanted - {r.id for r in rules}
        if unknown:
            print(f"error: unknown rule ids {sorted(unknown)}", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.id in wanted]

    linter = Linter(rules=rules)
    findings, n_files = linter.lint_paths([Path(p) for p in args.paths])

    if args.write_baseline is not None:
        write_baseline(args.write_baseline, findings)
        print(f"wrote {len(findings)} entries to {args.write_baseline}")
        return 0

    stale: list = []
    if args.baseline is not None and args.baseline.exists():
        findings, stale = apply_baseline(findings, load_baseline(args.baseline))

    if args.fmt == "json":
        print(
            json.dumps(
                {
                    "checked_files": n_files,
                    "findings": [f.to_json() for f in findings],
                    "stale_baseline_entries": stale,
                },
                indent=2,
            )
        )
    else:
        for finding in findings:
            print(finding.format_text())
        for entry in stale:
            print(
                f"warning: stale baseline entry {entry['fingerprint']} "
                f"({entry['rule']} {entry['path']}) — remove it",
                file=sys.stderr,
            )
        summary = f"{n_files} files checked, {len(findings)} finding(s)"
        print(summary if not findings else f"\n{summary}", file=sys.stderr)

    return 1 if findings else 0
