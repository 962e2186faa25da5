"""Pass 8 — fault-injection coverage drift (TSA801-TSA803).

Every crash-consistency guarantee is only as strong as the chaos harness's
coverage, and the harness reaches storage exclusively through
``FaultyStoragePlugin`` (``faults.py``). Plugin surface added after the
wrapper was written — the way ``list_prefix`` (gc) and the telemetry
artifact path were bolted on post-hoc — silently bypasses fault injection:
the op works in every chaos schedule because no schedule can touch it.
This pass pins the wrapper to the contract:

- **TSA801** — a public ``async`` method on the wrapped contract class
  (``StoragePlugin`` in ``io_types.py``) with no override on its wrapper
  (``FaultyStoragePlugin``) — calls fall through to the inner plugin
  uninjected.
- **TSA802** — a wrapper override that never routes through ``_guard`` and
  is not declared in ``faults.py``'s ``_PASSTHROUGH_OPS`` tuple (the
  reviewable allowlist for genuinely non-data-plane ops like ``close``).
- **TSA803** — a ``_guard("<op>", ...)`` literal not present in the
  ``_OPS`` tuple: a typo'd op class matches no rule, so that injection
  point silently never fires.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import AnalysisContext, Finding

# (contract class in io_types, wrapper class in faults)
_WRAP_PAIRS: Tuple[Tuple[str, str], ...] = (
    ("StoragePlugin", "FaultyStoragePlugin"),
)


def _class(tree: ast.AST, name: str) -> Optional[ast.ClassDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == name:
            return node
    return None


def _async_methods(cls: ast.ClassDef) -> Dict[str, int]:
    """{public async method name: line}."""
    return {
        node.name: node.lineno
        for node in cls.body
        if isinstance(node, ast.AsyncFunctionDef)
        and not node.name.startswith("_")
    }


def _methods(cls: ast.ClassDef) -> Dict[str, ast.AST]:
    return {
        node.name: node
        for node in cls.body
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
    }


def _string_tuple(tree: ast.AST, var: str) -> Optional[Set[str]]:
    """The string elements of a module-level ``var = ("a", "b", ...)``."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == var for t in node.targets
        ):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            out = set()
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    out.add(elt.value)
            return out
    return None


def _guard_calls(fn: ast.AST) -> List[ast.Call]:
    out = []
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "_guard"
        ):
            out.append(node)
    return out


def run(ctx: AnalysisContext) -> List[Finding]:
    findings: List[Finding] = []
    if ctx.io_types_path is None or ctx.faults_path is None:
        return findings
    contract_tree = ctx.tree(ctx.io_types_path)
    faults_tree = ctx.tree(ctx.faults_path)
    if contract_tree is None or faults_tree is None:
        return findings

    passthrough = _string_tuple(faults_tree, "_PASSTHROUGH_OPS") or set()
    ops = _string_tuple(faults_tree, "_OPS") or set()

    for contract_name, wrapper_name in _WRAP_PAIRS:
        contract = _class(contract_tree, contract_name)
        wrapper = _class(faults_tree, wrapper_name)
        if contract is None or wrapper is None:
            continue
        surface = _async_methods(contract)
        wrapped = _methods(wrapper)
        for method, line in sorted(surface.items()):
            if method not in wrapped:
                findings.append(
                    Finding(
                        path=ctx.io_types_path,
                        line=line,
                        code="TSA801",
                        message=(
                            f"`{contract_name}.{method}` has no override on "
                            f"`{wrapper_name}` ({ctx.faults_path}): calls "
                            "bypass fault injection — wrap it (route "
                            "through _guard) or declare it in "
                            "_PASSTHROUGH_OPS"
                        ),
                        key=f"unwrapped:{contract_name}.{method}",
                    )
                )
                continue
            if not _guard_calls(wrapped[method]) and method not in passthrough:
                findings.append(
                    Finding(
                        path=ctx.faults_path,
                        line=wrapped[method].lineno,
                        code="TSA802",
                        message=(
                            f"`{wrapper_name}.{method}` proxies without a "
                            "_guard injection point and is not declared in "
                            "_PASSTHROUGH_OPS — chaos schedules can never "
                            "fault this op"
                        ),
                        key=f"unguarded:{wrapper_name}.{method}",
                    )
                )

    # TSA803: every _guard op literal must be a declared op class.
    if ops:
        for node in ast.walk(faults_tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "_guard"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value not in ops
            ):
                findings.append(
                    Finding(
                        path=ctx.faults_path,
                        line=node.lineno,
                        code="TSA803",
                        message=(
                            f"_guard op `{node.args[0].value}` is not in "
                            "_OPS: no fault rule can ever match it, so the "
                            "injection point silently never fires"
                        ),
                        key=f"badop:{node.args[0].value}",
                    )
                )
    return findings
