"""Pass 6 — flow-sensitive resource balance (TSA601/TSA602).

The memory-budget ledger is the invariant the whole pipeline design rests
on: every ``budget.debit(...)`` (request admission, estimate correction)
must be matched — on EVERY path, including exception paths and early
returns — by a credit, or handed to an owner that guarantees the release
(the task tables ``_reap``/``_abort_inflight`` sweep, an ``outstanding``
counter a ``finally`` credits). The bug this class actually produced (PR 5:
failed staging tasks kept their reservation) was invisible to the earlier
passes — a *flow* bug, not a call-shape bug.

Each function containing an acquisition is walked with the
:class:`~dev.analyze.core.FlowWalker` engine, tracking the set of open
acquisitions per path. An acquisition is closed by:

- a release call (``.credit(X)`` matches the acquisition with the same
  amount expression, else the most recent one);
- a **handoff** that transfers ownership to a releasing owner: the amount
  (or a value it was derived from) is stored into a container
  (``tasks[t] = (req, cost, ...)``), appended/put onto one
  (``pending.append((fut, est))``), accumulated into a ledger counter
  (``outstanding += nbytes``), or returned to the caller.

Codes:

- **TSA601** — a path exits the function (early return, fall-through, or an
  unprotected raising statement) with an acquisition still open: the
  reservation leaks. A try whose handler/finally credits/releases protects
  its body's exceptional paths.
- **TSA602** — an ``await`` point while an acquisition is open and no
  protecting try encloses it: cancellation at that suspension strands the
  reservation even if the happy path balances (the PR 5 ``_reap`` shape).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import AnalysisContext, Finding, FlowWalker, dotted_name, iter_functions

_ACQUIRE_DEBIT = "debit"
_RELEASES = ("credit",)
_HANDOFF_METHODS = {
    "append", "appendleft", "add", "put", "put_nowait", "extend",
}


def _last_attr(call: ast.Call) -> Optional[str]:
    name = dotted_name(call.func)
    if name is not None:
        return name.rsplit(".", 1)[-1]
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return None


def _amount_expr(call: ast.Call) -> Optional[ast.expr]:
    return call.args[0] if call.args else None


def _names_in(node: ast.AST) -> Set[str]:
    return {
        n.id for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


class _Token:
    """One open acquisition, value-equal by (kind, site line, amount): the
    same site re-acquired on another loop pass is the same token, so loop
    states converge."""

    __slots__ = ("kind", "line", "amount_dump", "amount_names")

    def __init__(self, kind: str, call: ast.Call) -> None:
        self.kind = kind
        self.line = call.lineno
        amount = _amount_expr(call)
        self.amount_dump = ast.dump(amount) if amount is not None else ""
        self.amount_names = (
            frozenset(_names_in(amount)) if amount is not None else frozenset()
        )

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, _Token)
            and self.kind == other.kind
            and self.line == other.line
            and self.amount_dump == other.amount_dump
        )

    def __hash__(self) -> int:
        return hash((self.kind, self.line, self.amount_dump))


class _BalanceWalker(FlowWalker):
    def __init__(self, relpath: str, fn, derived: Dict[str, Set[str]]) -> None:
        self.relpath = relpath
        self.fn = fn
        # name -> names it was assigned from (one level): lets a handoff of
        # `buf` close a debit of `nbytes` when `nbytes = memoryview(buf).nbytes`.
        self.derived = derived
        self.findings: Dict[Tuple[int, str], Finding] = {}

    # -- token bookkeeping --------------------------------------------------
    def _token_matches_names(self, token: _Token, names: Set[str]) -> bool:
        if token.amount_names & names:
            return True
        for n in token.amount_names:
            if self.derived.get(n, set()) & names:
                return True
        return False

    def _close_release(self, state: Set[_Token], call: ast.Call) -> Set[_Token]:
        amount = _amount_expr(call)
        dump = ast.dump(amount) if amount is not None else None
        exact = [t for t in state if dump is not None and t.amount_dump == dump]
        if exact:
            victim = max(exact, key=lambda t: t.line)
            return state - {victim}
        if state:
            # No amount match (aggregated credit like `credit(outstanding)`):
            # release the most recent open acquisition.
            victim = max(state, key=lambda t: t.line)
            return state - {victim}
        return state

    def _apply_handoffs(self, stmt: ast.stmt, state: Set[_Token]) -> Set[_Token]:
        if not state:
            return state
        closed: Set[_Token] = set()
        if isinstance(stmt, ast.Assign):
            if any(
                isinstance(t, (ast.Subscript, ast.Attribute))
                for t in stmt.targets
            ):
                names = _names_in(stmt.value)
                closed |= {
                    t for t in state if self._token_matches_names(t, names)
                }
        elif isinstance(stmt, ast.AugAssign):
            names = _names_in(stmt.value)
            closed |= {t for t in state if self._token_matches_names(t, names)}
        elif isinstance(stmt, ast.Return) and stmt.value is not None:
            names = _names_in(stmt.value)
            closed |= {t for t in state if self._token_matches_names(t, names)}
        for call in (
            n for n in ast.walk(stmt) if isinstance(n, ast.Call)
        ):
            attr = _last_attr(call)
            if attr in _HANDOFF_METHODS:
                names = set()
                for arg in call.args:
                    names |= _names_in(arg)
                closed |= {
                    t for t in state if self._token_matches_names(t, names)
                }
        return state - closed

    # -- FlowWalker hooks ---------------------------------------------------
    def transfer(self, stmt: ast.stmt, state: frozenset) -> frozenset:
        out: Set[_Token] = set(state)
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            attr = _last_attr(node)
            if attr == _ACQUIRE_DEBIT:
                out.add(_Token(_ACQUIRE_DEBIT, node))
            elif attr in _RELEASES:
                out = self._close_release(out, node)
        out = self._apply_handoffs(stmt, out)
        return frozenset(out)

    def try_protects(self, trystmt: ast.Try) -> bool:
        bodies = list(trystmt.finalbody)
        for handler in trystmt.handlers:
            bodies.extend(handler.body)
        for stmt in bodies:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    break
                if isinstance(node, ast.Call):
                    attr = _last_attr(node)
                    if attr in _RELEASES:
                        return True
        return False

    def may_raise(self, stmt: ast.stmt) -> bool:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if isinstance(node, (ast.Call, ast.Raise)):
                return True
        return False

    # -- reporting ----------------------------------------------------------
    def _report(self, code: str, line: int, token: _Token, why: str) -> None:
        key = (token.line, code)
        if key in self.findings:
            return
        self.findings[key] = Finding(
            path=self.relpath,
            line=token.line,
            code=code,
            message=(
                f"budget debit in `{self.fn.name}` (line {token.line}) "
                f"{why} — credit/release it, protect it with a try/finally, "
                "or hand it to an owning container/counter that releases it"
            ),
            key=f"{self.fn.name}:{token.kind}:{token.line - self.fn.lineno}",
        )

    def on_await(self, stmt: ast.stmt, state: frozenset) -> None:
        for token in state:
            self._report(
                "TSA602",
                stmt.lineno,
                token,
                f"is open across the await at line {stmt.lineno}; "
                "cancellation there strands the reservation",
            )

    def on_unprotected_raise(self, stmt: ast.stmt, state: frozenset) -> None:
        for token in state:
            self._report(
                "TSA601",
                stmt.lineno,
                token,
                f"leaks if line {stmt.lineno} raises "
                "(no protecting try/finally encloses it)",
            )

    def on_exit(self, node: ast.AST, state: frozenset, how: str) -> None:
        where = (
            f"the return at line {node.lineno}"
            if how == "return"
            else "the end of the function"
        )
        for token in state:
            self._report(
                "TSA601", getattr(node, "lineno", token.line), token,
                f"is still open at {where}",
            )


def _derivations(fn) -> Dict[str, Set[str]]:
    """name -> names appearing in its (single-target) assignments, one
    level deep — enough to tie `nbytes = memoryview(buf).nbytes` to `buf`."""
    out: Dict[str, Set[str]] = {}
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
            continue
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            if isinstance(tgt, ast.Name):
                out.setdefault(tgt.id, set()).update(_names_in(node.value))
    return out


def _own_body_nodes(fn):
    """Nodes of ``fn``'s own body, stopping at nested function boundaries
    (nested defs are walked as their own functions)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def run(ctx: AnalysisContext) -> List[Finding]:
    findings: List[Finding] = []
    for relpath in ctx.lib_files:
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for fn in iter_functions(tree):
            has = any(
                isinstance(n, ast.Call)
                and _last_attr(n) == _ACQUIRE_DEBIT
                for n in _own_body_nodes(fn)
            )
            if not has:
                continue
            walker = _BalanceWalker(relpath, fn, _derivations(fn))
            walker.walk(fn)
            findings.extend(walker.findings.values())
    return findings
