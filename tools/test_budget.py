"""tier-1 budget guard — keep the fast test subset fast, honestly.

Two checks (both wired into CI's fast-tier job, and the marker scan also
runs inside tier-1 itself via ``tests/test_tier1_guard.py``):

1. **Marker scan** (static, no pytest run): every test function that
   spawns a subprocess (``run_sub`` / ``subprocess.*``) must carry
   ``@pytest.mark.slow`` — a new subprocess test silently landing in the
   fast tier is exactly how tier-1 wall clock rots.  Pre-existing bounded
   fast subprocess tests are grandfathered in :data:`ALLOW_FAST_SUBPROCESS`
   (file-level or per-test); additions to that list should carry a reason.
2. **Wall-clock budget**: given a ``--junit`` report from the tier-1 run
   (``pytest -q --junitxml=...``), the summed test time must stay under
   ``--budget-s``.
3. **Lint budget** (CI's ``lint`` job): given ``--lint-json`` (the
   ``repro.analysis/findings/v1`` artifact from ``tools/repro_lint.py
   --json --out ...``), its recorded ``wall_s`` must stay under
   ``--lint-budget-s`` — the static-analysis gate must stay cheap enough
   to never be worth skipping.

    PYTHONPATH=src python tools/test_budget.py \
        [--junit results/tier1.xml] [--budget-s 900] \
        [--lint-json results/lint_findings.json] [--lint-budget-s 120]

Exit status 0 = within budget and no unmarked subprocess tests.
"""
from __future__ import annotations

import argparse
import ast
import sys
import xml.etree.ElementTree as ET
from pathlib import Path
from typing import Dict, List, Set, Tuple

REPO = Path(__file__).resolve().parent.parent
TESTS_DIR = REPO / "tests"

# Default tier-1 wall-clock budget [s].  The seed suite runs ~5-6 min on
# the CI runner class; the budget leaves headroom without letting the fast
# tier double silently.
DEFAULT_BUDGET_S = 900.0

# Static-analysis gate budget [s]: repro_lint runs in ~1-2s locally; 120s
# leaves room for cold CI caches while still catching an analyzer that
# grew a quadratic scan.
DEFAULT_LINT_BUDGET_S = 120.0

# Fast tests allowed to spawn subprocesses: (file, test-name) with
# "*" = every test in the file.  Keep each entry justified.
ALLOW_FAST_SUBPROCESS: Set[Tuple[str, str]] = set()


def _is_slow_marker(dec: ast.expr) -> bool:
    """True for ``pytest.mark.slow`` / ``mark.slow`` decorators."""
    if isinstance(dec, ast.Call):
        dec = dec.func
    return isinstance(dec, ast.Attribute) and dec.attr == "slow" and (
        isinstance(dec.value, ast.Attribute) and dec.value.attr == "mark"
        or isinstance(dec.value, ast.Name) and dec.value.id == "mark")


def _spawn_names(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(module aliases of ``subprocess``, bare names that spawn) for a test
    module — so ``import subprocess as sp`` and
    ``from subprocess import run`` can't evade the scan."""
    aliases = {"subprocess"}
    names = {"run_sub"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "subprocess":
                    aliases.add(a.asname or a.name)
        elif isinstance(node, ast.ImportFrom) and node.module == "subprocess":
            names.update(a.asname or a.name for a in node.names)
    return aliases, names


def _spawns_subprocess(node: ast.AST, aliases: Set[str],
                       names: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in names:
            return True
        if isinstance(sub, ast.Attribute) and isinstance(sub.value, ast.Name) \
                and sub.value.id in aliases:
            return True
    return False


def _module_is_slow(tree: ast.Module) -> bool:
    """A module-level ``pytestmark = pytest.mark.slow`` covers every test."""
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "pytestmark"
                for t in node.targets):
            marks = (node.value.elts if isinstance(node.value, (ast.List,
                                                                ast.Tuple))
                     else [node.value])
            return any(_is_slow_marker(m) for m in marks)
    return False


def check_markers() -> List[str]:
    """Return a violation line per fast (unmarked) subprocess test."""
    violations: List[str] = []
    for path in sorted(TESTS_DIR.glob("test_*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        if _module_is_slow(tree):
            continue
        aliases, names = _spawn_names(tree)
        for node in tree.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not node.name.startswith("test"):
                continue
            if not _spawns_subprocess(node, aliases, names):
                continue
            if any(_is_slow_marker(d) for d in node.decorator_list):
                continue
            if ("*" in {t for f, t in ALLOW_FAST_SUBPROCESS
                        if f == path.name}
                    or (path.name, node.name) in ALLOW_FAST_SUBPROCESS):
                continue
            rel = (path.relative_to(REPO) if path.is_relative_to(REPO)
                   else path.name)
            violations.append(
                f"{rel}::{node.name} spawns a subprocess "
                "but has no @pytest.mark.slow (add the marker, or allowlist "
                "it in tools/test_budget.py with a reason)")
    return violations


def junit_times(junit: Path) -> Dict[str, float]:
    """testcase -> seconds from a junitxml report."""
    root = ET.parse(junit).getroot()
    out: Dict[str, float] = {}
    for case in root.iter("testcase"):
        name = f"{case.get('classname', '')}::{case.get('name', '')}"
        out[name] = float(case.get("time", 0.0))
    return out


def check_budget(junit: Path, budget_s: float) -> List[str]:
    times = junit_times(junit)
    total = sum(times.values())
    print(f"tier-1 test time: {total:.1f}s over {len(times)} tests "
          f"(budget {budget_s:.0f}s)")
    for name, t in sorted(times.items(), key=lambda kv: -kv[1])[:5]:
        print(f"  slowest: {t:7.1f}s  {name}")
    if total > budget_s:
        return [f"tier-1 fast subset took {total:.1f}s > budget "
                f"{budget_s:.0f}s — mark the new heavyweight tests slow or "
                "raise the budget deliberately"]
    return []


def check_lint_budget(lint_json: Path, budget_s: float) -> List[str]:
    """Validate the repro_lint findings artifact and price its wall clock."""
    import json

    sys.path.insert(0, str(REPO / "src"))
    from repro.analysis.findings import validate_findings

    payload = validate_findings(json.loads(lint_json.read_text()))
    wall = float(payload["wall_s"])
    print(f"repro-lint wall clock: {wall:.1f}s "
          f"(budget {budget_s:.0f}s, clean={payload['clean']})")
    if wall > budget_s:
        return [f"repro_lint took {wall:.1f}s > budget {budget_s:.0f}s — "
                "the static-analysis gate must stay cheap"]
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--junit", default="",
                    help="junitxml report of the tier-1 run; omitting it "
                         "skips the wall-clock check (marker scan only)")
    ap.add_argument("--budget-s", type=float, default=DEFAULT_BUDGET_S)
    ap.add_argument("--lint-json", default="",
                    help="repro_lint --json artifact; omitting it skips "
                         "the lint wall-clock check")
    ap.add_argument("--lint-budget-s", type=float,
                    default=DEFAULT_LINT_BUDGET_S)
    args = ap.parse_args(argv)

    problems = check_markers()
    if args.junit:
        problems += check_budget(Path(args.junit), args.budget_s)
    if args.lint_json:
        problems += check_lint_budget(Path(args.lint_json),
                                      args.lint_budget_s)
    for p in problems:
        print(f"BUDGET GUARD: {p}", file=sys.stderr)
    if not problems:
        print("test budget guard: ok")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
