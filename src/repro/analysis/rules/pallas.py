"""pallas-rules: kernel hygiene for the TPU Pallas layer.

**grid divisibility** — a ``pallas_call`` grid computed with ``//``
silently drops the remainder: ``grid=(S // block,)`` with
``S % block != 0`` skips the tail elements and produces wrong results
with no error.  Inside any function that invokes ``pl.pallas_call`` — or
constructs a ``*GridSpec`` (e.g. ``pltpu.PrefetchScalarGridSpec``), which
carries a grid to a ``pallas_call`` elsewhere — every floor division must
be paired with a matching ``lhs % rhs`` check (assert or comparison) over
the same operands in the same function.  Floor divisions inside
``lambda`` index maps are exempt — Pallas index maps legitimately map
block indices with ``//``.

Mosaic's block-tiling rules are not checked here: the TPU compile
rehearsal in ``tests/test_tpu_compile.py`` applies the compiler itself.
"""
from __future__ import annotations

import ast

from repro.analysis.framework import Project, Rule, Violation

SRC_GLOB = "src/repro/**/*.py"


def _nodes_in_lambdas(func: ast.FunctionDef) -> set[int]:
    """ids of AST nodes nested inside any Lambda in ``func``."""
    inside: set[int] = set()
    for node in ast.walk(func):
        if isinstance(node, ast.Lambda):
            for sub in ast.walk(node):
                inside.add(id(sub))
    return inside


def _uses_pallas_call(func: ast.FunctionDef) -> bool:
    """True if ``func`` feeds a Pallas grid: calls ``pallas_call`` itself
    or constructs a ``*GridSpec`` (e.g. ``pltpu.PrefetchScalarGridSpec``)
    that a ``pallas_call`` elsewhere consumes — a grid built with an
    unchecked ``//`` is just as wrong when it reaches the kernel through
    a grid-spec object as through the ``grid=`` kwarg."""
    for node in ast.walk(func):
        if not isinstance(node, ast.Call):
            continue
        callee = node.func
        name = callee.attr if isinstance(callee, ast.Attribute) else (
            callee.id if isinstance(callee, ast.Name) else "")
        if name == "pallas_call" or name.endswith("GridSpec"):
            return True
    return False


class PallasRulesRule(Rule):
    name = "pallas-rules"
    description = ("pallas_call grids built with // need a matching % "
                   "divisibility check")

    def check(self, project: Project) -> list[Violation]:
        out: list[Violation] = []
        for path in project.glob(SRC_GLOB):
            tree = project.tree(path)
            if tree is None:
                continue
            out.extend(self._check_divisibility(path, tree))
        return out

    # ------------------------------------------------------------------
    # floor divisions near pallas_call need % checks
    # ------------------------------------------------------------------
    def _check_divisibility(self, path: str,
                            tree: ast.AST) -> list[Violation]:
        out: list[Violation] = []
        for func in [n for n in ast.walk(tree)
                     if isinstance(n, ast.FunctionDef)]:
            if not _uses_pallas_call(func):
                continue
            in_lambda = _nodes_in_lambdas(func)
            mods: set[tuple[str, str]] = set()
            floordivs: list[ast.BinOp] = []
            for node in ast.walk(func):
                if not isinstance(node, ast.BinOp):
                    continue
                try:
                    operands = (ast.unparse(node.left),
                                ast.unparse(node.right))
                except Exception:
                    continue
                if isinstance(node.op, ast.Mod):
                    mods.add(operands)
                elif isinstance(node.op, ast.FloorDiv) \
                        and id(node) not in in_lambda:
                    floordivs.append(node)
            for node in floordivs:
                operands = (ast.unparse(node.left), ast.unparse(node.right))
                if operands not in mods:
                    out.append(self.violation(
                        path, node,
                        f"`{operands[0]} // {operands[1]}` in "
                        f"pallas_call-using `{func.name}` has no matching "
                        f"`{operands[0]} % {operands[1]}` divisibility "
                        "check — a non-dividing shape silently drops the "
                        "tail block"))
        return out
