"""reprolint: fixture expectations per rule, historical regressions, the
suppression/baseline machinery, the layer map, and a whole-repo smoke run.

Every rule ships a true-positive (``tp.py``) and false-positive (``fp.py``)
fixture under ``tools/reprolint/testdata/<rule>/``; this module asserts the
TP is flagged by exactly that rule and the FP produces *zero* findings, so
both the detection and the precision of each rule are pinned.
"""
import ast
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from tools.reprolint import toml_compat  # noqa: E402
from tools.reprolint.engine import (  # noqa: E402
    Finding,
    Linter,
    apply_baseline,
    load_baseline,
    write_baseline,
)
from tools.reprolint.layers import LayerMap  # noqa: E402
from tools.reprolint.rules import all_rules  # noqa: E402

TESTDATA = ROOT / "tools" / "reprolint" / "testdata"
RULE_IDS = ("rl001", "rl002", "rl003", "rl004", "rl005", "rl006", "rl007")

# RL005 keys on the module's repo path, so its fixtures are linted under
# synthetic in-tree paths rather than their on-disk testdata location.
_SYNTHETIC_PATHS = {
    ("rl005", "tp"): "src/repro/core/bad_upward.py",
    ("rl005", "fp"): "src/repro/serve/good_imports.py",
}


@pytest.fixture(scope="module")
def linter():
    return Linter(all_rules(), repo_root=ROOT)


def _lint_fixture(linter, rule, kind):
    path = TESTDATA / rule / f"{kind}.py"
    lint_path = _SYNTHETIC_PATHS.get((rule, kind), str(path))
    return linter.lint_source(path.read_text(), lint_path)


# ----------------------------------------------------------- per-rule fixtures
@pytest.mark.parametrize("rule", RULE_IDS)
def test_true_positive_fixture_is_flagged(linter, rule):
    findings = _lint_fixture(linter, rule, "tp")
    hits = [f for f in findings if f.rule == rule.upper()]
    assert hits, f"{rule}/tp.py: expected {rule.upper()} findings, got none"
    # the fixture marks each expected finding with an `# RL00x:` comment
    source = (TESTDATA / rule / "tp.py").read_text()
    assert rule.upper() + ":" in source  # fixture documents what it expects


@pytest.mark.parametrize("rule", RULE_IDS)
def test_false_positive_fixture_is_clean(linter, rule):
    findings = _lint_fixture(linter, rule, "fp")
    assert findings == [], (
        f"{rule}/fp.py must lint clean, got: "
        + "; ".join(f.format_text() for f in findings)
    )


# ------------------------------------------------------ historical regressions
def test_pr4_float_mu_guess_regression_is_flagged(linter):
    """PR 4 shipped ``float(mu_guess)`` on a traced mean inside ``fit``;
    RL001 must catch that shape of bug forever."""
    path = TESTDATA / "regressions" / "pr4_float_mu_guess.py"
    findings = linter.lint_source(path.read_text(), str(path))
    assert any(
        f.rule == "RL001" and "float" in f.snippet for f in findings
    ), findings


def test_pr7_cond_dtype_regression_is_flagged(linter):
    """PR 7 hit a ``lax.cond`` whose hold branch returned a different dtype
    than the refit branch; RL003 must catch structural branch drift."""
    path = TESTDATA / "regressions" / "pr7_cond_dtype.py"
    findings = linter.lint_source(path.read_text(), str(path))
    assert any(f.rule == "RL003" for f in findings), findings


# ------------------------------------------------------------ taint precision
def test_static_config_through_call_graph_stays_clean(linter):
    """Call-site-aware taint: a helper reached via the call graph whose
    branching argument is jit-static at the call site must not trip RL007
    (this is the service/gibbs `config` threading pattern)."""
    src = (
        "import functools\n"
        "import jax\n"
        "\n"
        "def _body(x, config):\n"
        "    if config.use_fast_path:\n"
        "        return x * 2.0\n"
        "    return x + x\n"
        "\n"
        "@functools.partial(jax.jit, static_argnames=('config',))\n"
        "def tick(x, config):\n"
        "    return _body(x, config)\n"
    )
    assert linter.lint_source(src, "src/repro/serve/example.py") == []


def test_traced_value_through_call_graph_is_still_flagged(linter):
    """...but the same helper branching on a value that IS traced at the
    call site must still be flagged."""
    src = (
        "import jax\n"
        "\n"
        "def _body(x, gate):\n"
        "    if gate:\n"
        "        return x * 2.0\n"
        "    return x + x\n"
        "\n"
        "@jax.jit\n"
        "def tick(x):\n"
        "    return _body(x, x.sum() > 0)\n"
    )
    findings = linter.lint_source(src, "src/repro/serve/example.py")
    assert any(f.rule == "RL007" for f in findings), findings


# --------------------------------------------------------------- suppressions
_SUPPRESSED_SRC = (
    "import jax\n"
    "\n"
    "@jax.jit\n"
    "def f(x):\n"
    "    return float(x)  # reprolint: disable=RL001 -- {why}\n"
)


def test_justified_suppression_silences_the_finding(linter):
    src = _SUPPRESSED_SRC.format(why="fixture: documented exception")
    assert linter.lint_source(src, "x.py") == []


def test_bare_suppression_raises_meta_finding(linter):
    src = _SUPPRESSED_SRC.replace(" -- {why}", "")
    findings = linter.lint_source(src, "x.py")
    assert [f.rule for f in findings] == ["RL000"]
    assert "justification" in findings[0].message


def test_directive_inside_string_literal_is_not_a_directive(linter):
    src = 'HELP = "# reprolint: disable=RL001 -- example syntax"\n'
    assert linter.lint_source(src, "x.py") == []


def test_unused_suppression_raises_meta_finding(linter):
    src = "x = 1  # reprolint: disable=RL001 -- nothing here needs it\n"
    findings = linter.lint_source(src, "x.py")
    assert [f.rule for f in findings] == ["RL000"]
    assert "unused suppression" in findings[0].message


# -------------------------------------------------------------------- baseline
def _finding(line=3):
    return Finding(
        rule="RL001", path="src/x.py", line=line, col=4,
        message="m", snippet="y = float(x)",
    )


def test_fingerprint_is_line_number_insensitive():
    assert _finding(line=3).fingerprint == _finding(line=99).fingerprint


def test_baseline_roundtrip_filters_and_reports_stale(tmp_path):
    known, new = _finding(), Finding(
        rule="RL006", path="src/y.py", line=8, col=0,
        message="m", snippet="a = jax.random.normal(key, ())",
    )
    baseline_path = tmp_path / "baseline.json"
    write_baseline(baseline_path, [known])
    baseline = load_baseline(baseline_path)

    kept, stale = apply_baseline([known, new], baseline)
    assert kept == [new] and stale == []

    kept, stale = apply_baseline([new], baseline)  # known finding fixed
    assert kept == [new]
    assert [e["fingerprint"] for e in stale] == [known.fingerprint]


def test_baseline_rejects_unknown_version(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 2, "entries": []}))
    with pytest.raises(ValueError, match="version"):
        load_baseline(p)


# ------------------------------------------------------------------- layer map
def test_layer_map_flags_upward_and_allows_downward():
    layer_map = LayerMap.load()
    up = layer_map.violation("repro.core.frontier", "repro.sched.objectives")
    assert up is not None and "upward import" in up
    assert layer_map.violation("repro.sched.objectives", "repro.core.frontier") is None
    assert layer_map.violation("repro.serve.service", "repro.hier.pool") is None


def test_core_never_imports_sched():
    """RL005 exempts deferred in-function imports; the core layer takes no
    such exemption for sched: no module under `src/repro/core` imports
    `repro.sched`, at module level or inside a function."""
    found = []
    for path in sorted((ROOT / "src" / "repro" / "core").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if isinstance(node, ast.Import):
                names = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                parts = [node.module] if node.module else []
                if node.level:  # relative: resolve against the module's package
                    pkg = ["repro", *path.relative_to(ROOT / "src" / "repro").parts[:-1]]
                    parts = pkg[: len(pkg) - node.level + 1] + parts
                module = ".".join(parts)
                names = [module] + [f"{module}.{a.name}" for a in node.names]
            else:
                continue
            found += [f"{path.name}:{node.lineno} {name}" for name in names
                      if name == "repro.sched" or name.startswith("repro.sched.")]
    assert found == []


def test_importing_core_does_not_import_sched():
    """Importing the core layer must not pull the sched layer into the
    process."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.core; "
         "bad = [m for m in sys.modules if m.startswith('repro.sched')]; "
         "sys.exit(1 if bad else 0)"],
        cwd=ROOT, env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_layer_doc_section_in_sync():
    """docs/architecture.md's generated table must match layers.toml —
    regenerate with `python -m tools.reprolint --sync-layer-docs`."""
    assert LayerMap.load().sync_doc(ROOT / "docs" / "architecture.md", write=False)


def test_toml_subset_parser_matches_tomllib():
    text = (ROOT / "tools" / "reprolint" / "layers.toml").read_text()
    subset = toml_compat.parse_subset(text)
    tomllib = pytest.importorskip("tomllib")
    assert subset == tomllib.loads(text)


# ------------------------------------------------------------------ smoke gate
def test_shipped_tree_lints_clean():
    """The acceptance gate: reprolint over the shipped tree exits 0 with no
    baseline.  A finding here means a new invariant violation landed."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.reprolint",
         "src", "tests", "--format=json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["findings"] == [] and report["checked_files"] > 50
