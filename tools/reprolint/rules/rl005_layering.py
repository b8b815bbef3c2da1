"""RL005 — import-layering enforcement from the machine-readable layer map.

``tools/reprolint/layers.toml`` orders the first-level packages of
``repro`` bottom -> top; a *module-level* import may only target the same or
a lower layer.  Deferred in-function imports are exempt by design: they
cannot create import cycles and are the repo's sanctioned escape hatch for
acyclic back-references (``kernels/ops.py``'s duck-typed ShardingConfig
import, ``sched``'s lazy hierarchical path).

The rule caught a legacy partitioner wrapper in ``repro.core`` importing
``repro.sched`` at module level (core -> sched is upward); the wrapper has
since been deleted.
"""
from __future__ import annotations

import ast
from pathlib import PurePosixPath
from typing import List, Optional

from ..context import ModuleContext
from ..engine import Finding
from ..layers import LayerMap
from . import Rule


def _module_name_for_path(path: str, root_package: str):
    """``src/repro/core/frontier.py`` -> (``repro.core.frontier``, False);
    an ``__init__.py`` maps to its package name with ``is_package=True``."""
    parts = list(PurePosixPath(path.replace("\\", "/")).parts)
    if root_package not in parts:
        return None, False
    parts = parts[parts.index(root_package):]
    if parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    is_package = parts[-1] == "__init__"
    if is_package:
        parts = parts[:-1]
    return ".".join(parts), is_package


class LayeringViolation(Rule):
    id = "RL005"
    title = "module-level import targets a higher layer"

    def __init__(self, layer_map: Optional[LayerMap] = None):
        self.layer_map = layer_map if layer_map is not None else LayerMap.load()

    def check(self, ctx: ModuleContext) -> List[Finding]:
        importer, is_package = _module_name_for_path(
            ctx.path, self.layer_map.root_package
        )
        if importer is None:
            return []
        importer_pkg = self.layer_map.package_of_module(importer)
        if importer_pkg is None or self.layer_map.rank(importer_pkg) is None:
            return []

        findings: List[Finding] = []
        for node in ctx.tree.body:  # module level only: deferred imports exempt
            for imported in self._imported_modules(node, importer, is_package):
                message = self.layer_map.violation(importer, imported)
                if message:
                    findings.append(self.finding(ctx, node, message))
        return findings

    def _imported_modules(
        self, node: ast.stmt, importer: str, is_package: bool
    ) -> List[str]:
        root = self.layer_map.root_package
        if isinstance(node, ast.Import):
            return [a.name for a in node.names if a.name.startswith(f"{root}.")]
        if isinstance(node, ast.ImportFrom):
            if node.level == 0:
                if node.module == root:
                    return [f"{root}.{a.name}" for a in node.names]
                if node.module and node.module.startswith(f"{root}."):
                    return [node.module]
                return []
            # Relative import: resolve against the importer's package.
            package = importer.split(".") if is_package else importer.split(".")[:-1]
            base = package[: len(package) - (node.level - 1)]
            if node.module:
                base = base + node.module.split(".")
            target = ".".join(base)
            return [target] if target == root or target.startswith(f"{root}.") else []
        return []
