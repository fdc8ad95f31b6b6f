"""Mesh/collective axis-consistency analyzer (MX1xx).

A ``jax.lax.psum(..., "data")`` with an axis name no mesh ever declares
fails only at trace time, inside a shard_map, usually three minutes into a
run.  This pass makes the binding statically checkable:

- Pass 1 collects every axis name the repo *declares* — string literals
  inside ``Mesh(...)``/``make_mesh(...)`` constructions, ``axis_names=``
  keyword tuples, and ``PartitionSpec``/``P`` literals.  The declared set
  is repo-global: ``distributed/trainer.py`` builds the ``(data,)`` and
  ``(nodes, data)`` meshes whose axes ``distributed/collectives.py``
  reduces over, and ``distributed/pipeline.py``'s ``(pipe, data)`` grid
  declares the ``pipe`` stage axis its per-stage flat meshes slice out of.
- Pass 2 audits every collective call (``psum``, ``psum_scatter``,
  ``all_gather``, ``ppermute``, ``pmean``, ``pmax``, ``pmin``,
  ``all_to_all``, ``axis_index``):

  - **MX101** — a *literal* axis name (or tuple member) not in the
    declared set: the collective can never bind.
  - **MX102** — no axis argument at all (neither positional nor
    ``axis_name=``): the call is malformed.

Axis names passed as variables are skipped — the strategy zoo in
``collectives.py`` takes the axis as a parameter, and resolving dataflow
is out of scope for a lint pass; the rule catches the literal typo case
the issue names (the common way this bug is written).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.findings import Finding

MESH_CTORS = {"Mesh", "make_mesh", "AbstractMesh"}
SPEC_CTORS = {"PartitionSpec", "P"}
COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "psum_scatter",
               "all_gather", "ppermute", "all_to_all", "axis_index"}


def _last(name_node: ast.AST) -> Optional[str]:
    if isinstance(name_node, ast.Attribute):
        return name_node.attr
    if isinstance(name_node, ast.Name):
        return name_node.id
    return None


def _str_literals(node: ast.AST) -> Iterable[str]:
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n.value


def declared_axes(src: str, path: str = "<src>") -> Set[str]:
    """Axis names bound by mesh/PartitionSpec declarations in one module."""
    axes: Set[str] = set()
    tree = ast.parse(src, filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = _last(node.func)
            if fn in MESH_CTORS | SPEC_CTORS:
                for s in _str_literals(node):
                    axes.add(s)
        if isinstance(node, ast.keyword) and node.arg == "axis_names":
            for s in _str_literals(node.value):
                axes.add(s)
    return axes


def _axis_arg(call: ast.Call) -> Tuple[bool, Optional[ast.AST]]:
    """(present, node) for a collective's axis argument.  Positional slot 1
    (after the operand; slot 0 for axis_index) or ``axis_name=``."""
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return True, kw.value
    fn = _last(call.func)
    slot = 0 if fn == "axis_index" else 1
    if len(call.args) > slot:
        return True, call.args[slot]
    return False, None


class _CollectiveVisitor(ast.NodeVisitor):
    def __init__(self, path: str, axes: Set[str]):
        self.path = path
        self.axes = axes
        self.stack: List[str] = []
        self.findings: List[Finding] = []

    @property
    def context(self) -> str:
        return ".".join(self.stack) if self.stack else "<module>"

    def _scoped(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def visit_FunctionDef(self, node): self._scoped(node)
    def visit_AsyncFunctionDef(self, node): self._scoped(node)
    def visit_ClassDef(self, node): self._scoped(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = _last(node.func)
        if fn in COLLECTIVES:
            # only jax.lax-style call sites: require an attribute access
            # (lax.psum / jax.lax.psum) or a bare name imported from lax —
            # bare-name heuristic accepted; false negatives only.
            present, axis = _axis_arg(node)
            if not present:
                self.findings.append(Finding(
                    path=self.path, line=node.lineno, code="MX102",
                    message=f"{fn}() without an axis argument",
                    context=self.context))
            else:
                names: List[str] = []
                if isinstance(axis, ast.Constant) and isinstance(
                        axis.value, str):
                    names = [axis.value]
                elif isinstance(axis, (ast.Tuple, ast.List)) and all(
                        isinstance(e, ast.Constant)
                        and isinstance(e.value, str) for e in axis.elts):
                    names = [e.value for e in axis.elts]
                for name in names:
                    if name not in self.axes:
                        self.findings.append(Finding(
                            path=self.path, line=node.lineno, code="MX101",
                            message=f"{fn}(axis={name!r}): axis never "
                                    f"declared by any mesh (declared: "
                                    f"{sorted(self.axes) or 'none'})",
                            context=self.context))
        self.generic_visit(node)


def analyze_sources(pairs: Sequence[Tuple[str, str]]) -> List[Finding]:
    """Two-pass over (path, source) modules: collect the repo-global axis
    set, then audit every collective call against it."""
    axes: Set[str] = set()
    for path, src in pairs:
        axes |= declared_axes(src, path)
    out: List[Finding] = []
    for path, src in pairs:
        v = _CollectiveVisitor(path, axes)
        v.visit(ast.parse(src, filename=path))
        out.extend(v.findings)
    return sorted(out)


def analyze(root) -> List[Finding]:
    root = Path(root)
    pairs = [(p.relative_to(root).as_posix(), p.read_text())
             for p in sorted((root / "src" / "repro").rglob("*.py"))]
    return analyze_sources(pairs)
