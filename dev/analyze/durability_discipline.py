"""Pass 10 — durability discipline (TSA1001-TSA1004).

The lifecycle layer's crash-consistency story rests on ordering rules no
interpreter enforces: temp-write→``os.replace`` is THE commit point for
every durable object; the catalog record — the publish — lands only after
``.snapshot_metadata`` — the data commit; GC deletes only what a keep-set
membership check excluded; and every commit point stays reachable by a
``faults.py`` kill-point so chaos schedules can crash exactly there. This
pass makes each rule a gate (``dev/crash_explorer.py`` is its runtime
cross-check):

- **TSA1001** — a persistent-state mutation bypassing the atomic-commit
  idiom: a write-mode ``open()`` whose target is not a temp path and is
  never ``os.replace``d into place within the same function. Temp-write→
  rename, plugin-routed writes, and documented fail-open sidecars
  (``# noqa: TSA1001`` + rationale) stay quiet.
- **TSA1002** — publish-before-payload: a catalog/step-telemetry append
  reachable on a CFG path not dominated by the corresponding
  ``_write_snapshot_metadata`` data commit (``core.FlowWalker``).
- **TSA1003** — a delete issued from GC/retention/eviction code
  (function name matching ``gc``/``evict``/``retain``) with no preceding
  keep-set/pin membership check anywhere in the function.
- **TSA1004** — crash-surface drift: every function performing a direct
  durable mutation (``os.replace``/``rename``/``link``/``remove``/
  ``unlink``, or a mutating call on a storage plugin) must be pinned in
  ``faults.py``'s ``_CRASH_SURFACE`` table to a kill-point op class in
  ``_OPS`` (or declared ``fail-open``), and every table entry must still
  name a discovered site — the commit-point inventory and the chaos
  surface can never silently diverge.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import AnalysisContext, Finding, FlowWalker, dotted_name

# ----------------------------------------------------------- shared helpers

_WRITE_MODES_RE = re.compile(r"^[wax]|\+")

# Publish calls (the catalog-visible side) -> the data commit that must
# dominate them on every CFG path.
_PUBLISH_TO_COMMIT: Tuple[Tuple[str, str], ...] = (
    ("_append_catalog_record", "_write_snapshot_metadata"),
    ("_append_step_telemetry_record", "_write_snapshot_metadata"),
)
_PUBLISH_NAMES = {p for p, _ in _PUBLISH_TO_COMMIT}
_COMMIT_NAMES = {c for _, c in _PUBLISH_TO_COMMIT}

_GC_SCOPE_RE = re.compile(r"(?:^|_)(?:gc|evict|eviction|retain|retention)")
_KEEP_NAME_RE = re.compile(r"keep|retain|pinned|\bpin\b", re.IGNORECASE)

# Direct filesystem mutations that constitute (or finish) a commit point.
_OS_MUTATIONS = {
    "os.replace", "os.rename", "os.link", "os.remove", "os.unlink",
}
# Mutating methods of the StoragePlugin surface; a call through a receiver
# whose name mentions storage/plugin is a plugin-routed durable mutation.
_PLUGIN_MUTATIONS = {"write", "sync_write", "delete", "link_in"}
_PLUGIN_RECEIVER_RE = re.compile(r"storage|plugin")

# Files exempt from the TSA1004 inventory: the injection machinery itself
# and the journal that merely observes effects.
_INVENTORY_EXEMPT_BASENAMES = {"faults.py", "effect_journal.py"}


def _call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


def _last_attr(name: Optional[str]) -> Optional[str]:
    if name is None:
        return None
    return name.rsplit(".", 1)[-1]


def _top_level_functions(tree: ast.AST):
    """(qualname, function node) for every module-level function and every
    method of a module-level class — the granularity at which commit
    points are named. Nested defs stay inside their owner's subtree."""
    for node in tree.body if isinstance(tree, ast.Module) else []:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield f"{node.name}.{sub.name}", sub


def _expr_text(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is total on real trees
        return ""


def _looks_temp(node: ast.AST) -> bool:
    """Whether an open() target expression names a temp path: a variable /
    attribute whose name mentions tmp, or any literal part containing
    '.tmp' (the `f"{path}.tmp.{pid}"` idiom)."""
    text = _expr_text(node).lower()
    return "tmp" in text


def _open_mode(call: ast.Call) -> Optional[str]:
    """The mode literal of an ``open()`` call, or None when unknowable
    statically (default "r" returns "r")."""
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            return str(kw.value.value)
    if len(call.args) >= 2:
        if isinstance(call.args[1], ast.Constant):
            return str(call.args[1].value)
        return None
    return "r"


# ------------------------------------------------------------------ TSA1001


def _tsa1001(ctx: AnalysisContext, relpath: str) -> List[Finding]:
    tree = ctx.tree(relpath)
    if tree is None:
        return []
    findings: List[Finding] = []
    for qualname, fn in _top_level_functions(tree):
        # Names os.replace()d into place anywhere in this function: a
        # write to one is the temp leg of a temp->rename commit even when
        # the variable is not named like a temp.
        replaced: Set[str] = set()
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Call)
                and _call_name(node) in ("os.replace", "os.rename")
                and node.args
            ):
                replaced.add(_expr_text(node.args[0]))
        for node in ast.walk(fn):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "open"
                and node.args
            ):
                continue
            mode = _open_mode(node)
            if mode is not None and not _WRITE_MODES_RE.search(mode):
                continue
            target = node.args[0]
            if _looks_temp(target):
                continue
            if _expr_text(target) in replaced:
                continue
            findings.append(
                Finding(
                    path=relpath,
                    line=node.lineno,
                    code="TSA1001",
                    message=(
                        f"`{qualname}` opens `{_expr_text(target)}` for "
                        "writing in place: a crash mid-write leaves a torn "
                        "final object. Write a temp path and os.replace() "
                        "it in (or route through a StoragePlugin write); "
                        "a deliberately non-atomic fail-open sidecar needs "
                        "`# noqa: TSA1001` + a rationale"
                    ),
                    key=f"bare-open:{qualname}",
                )
            )
    return findings


# ------------------------------------------------------------------ TSA1002


class _PublishWalker(FlowWalker):
    """Token 'commit' is set by a data-commit call; a publish call in a
    state without it is reachable before the payload is durable."""

    def __init__(self, on_violation) -> None:
        self._on_violation = on_violation

    @staticmethod
    def _calls_in(stmt: ast.stmt) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                attr = _last_attr(_call_name(node))
                if attr is not None:
                    out.add(attr)
        return out

    def transfer(self, stmt: ast.stmt, state: frozenset) -> frozenset:
        calls = self._calls_in(stmt)
        if calls & _PUBLISH_NAMES and "commit" not in state:
            self._on_violation(stmt, sorted(calls & _PUBLISH_NAMES))
        if calls & _COMMIT_NAMES:
            return state | {"commit"}
        return state


def _tsa1002(ctx: AnalysisContext, relpath: str) -> List[Finding]:
    tree = ctx.tree(relpath)
    if tree is None:
        return []
    findings: List[Finding] = []
    for qualname, fn in _top_level_functions(tree):
        leaf = qualname.rsplit(".", 1)[-1]
        if leaf in _PUBLISH_NAMES:
            continue  # the publish implementation itself (and its callees)
        has_publish = any(
            isinstance(n, ast.Call)
            and _last_attr(_call_name(n)) in _PUBLISH_NAMES
            for n in ast.walk(fn)
        )
        if not has_publish:
            continue
        seen: Set[Tuple[int, str]] = set()

        def on_violation(stmt: ast.stmt, names: List[str]) -> None:
            for name in names:
                if (stmt.lineno, name) in seen:
                    continue
                seen.add((stmt.lineno, name))
                findings.append(
                    Finding(
                        path=relpath,
                        line=stmt.lineno,
                        code="TSA1002",
                        message=(
                            f"`{qualname}` publishes via `{name}` on a "
                            "path not dominated by the data commit "
                            "(`_write_snapshot_metadata`): a crash after "
                            "the publish leaves a catalog-visible record "
                            "for a snapshot that was never durable"
                        ),
                        key=f"publish-before-commit:{qualname}:{name}",
                    )
                )

        _PublishWalker(on_violation).walk(fn)
    return findings


# ------------------------------------------------------------------ TSA1003


def _is_delete_call(node: ast.Call) -> bool:
    name = _call_name(node)
    if name in ("os.remove", "os.unlink"):
        return True
    attr = _last_attr(name)
    return attr in ("delete", "delete_many")


def _tsa1003(ctx: AnalysisContext, relpath: str) -> List[Finding]:
    tree = ctx.tree(relpath)
    if tree is None:
        return []
    findings: List[Finding] = []
    for qualname, fn in _top_level_functions(tree):
        leaf = qualname.rsplit(".", 1)[-1].lower()
        if not _GC_SCOPE_RE.search(leaf):
            continue
        # Keep-set membership checks: `x (not) in <keep-ish>` compares
        # anywhere in the function (nested closures included — GC fans its
        # pre-filtered waves out through them).
        guard_lines = [
            node.lineno
            for node in ast.walk(fn)
            if isinstance(node, ast.Compare)
            and any(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops)
            and _KEEP_NAME_RE.search(_expr_text(node))
        ]
        first_guard = min(guard_lines) if guard_lines else None
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call) and _is_delete_call(node)):
                continue
            if first_guard is not None and first_guard <= node.lineno:
                continue
            findings.append(
                Finding(
                    path=relpath,
                    line=node.lineno,
                    code="TSA1003",
                    message=(
                        f"GC-scope function `{qualname}` deletes with no "
                        "preceding keep-set/pin membership check: nothing "
                        "bounds what this sweep can destroy — filter the "
                        "victims through the keep-set (`p not in keep`) "
                        "or a pin check first"
                    ),
                    key=f"ungated-delete:{qualname}",
                )
            )
            break  # one finding per function
    return findings


# ------------------------------------------------------------------ TSA1004


def _pair_tuple(
    tree: ast.AST, var: str
) -> Optional[List[Tuple[str, str, int]]]:
    """[(site, op, line)] of a module-level ``var = (("a", "b"), ...)``."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == var for t in node.targets
        ):
            continue
        if not isinstance(node.value, (ast.Tuple, ast.List)):
            return None
        out = []
        for elt in node.value.elts:
            if (
                isinstance(elt, (ast.Tuple, ast.List))
                and len(elt.elts) == 2
                and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, str)
                    for e in elt.elts
                )
            ):
                out.append(
                    (elt.elts[0].value, elt.elts[1].value, elt.lineno)
                )
        return out
    return None


def discover_commit_points(
    ctx: AnalysisContext,
) -> Dict[str, Tuple[str, int]]:
    """The commit-point inventory: ``{site: (relpath, line)}`` where site is
    ``<basename>:<qualname>`` of every function performing a direct
    durable mutation. The reviewable mirror lives in ``faults.py``'s
    ``_CRASH_SURFACE``; :func:`run` pins the two to each other."""
    inventory: Dict[str, Tuple[str, int]] = {}
    for relpath in ctx.lib_files:
        base = os.path.basename(relpath)
        if base in _INVENTORY_EXEMPT_BASENAMES:
            continue
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for qualname, fn in _top_level_functions(tree):
            line = None
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                if name in _OS_MUTATIONS:
                    line = node.lineno
                    break
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _PLUGIN_MUTATIONS
                    and _PLUGIN_RECEIVER_RE.search(
                        _expr_text(node.func.value).lower()
                    )
                ):
                    line = node.lineno
                    break
            if line is not None:
                inventory[f"{base}:{qualname}"] = (relpath, line)
    return inventory


def _tsa1004(ctx: AnalysisContext) -> List[Finding]:
    if ctx.faults_path is None:
        return []
    faults_tree = ctx.tree(ctx.faults_path)
    if faults_tree is None:
        return []
    from .fault_coverage import _string_tuple

    ops = (_string_tuple(faults_tree, "_OPS") or set()) | {"fail-open"}
    surface = _pair_tuple(faults_tree, "_CRASH_SURFACE")
    inventory = discover_commit_points(ctx)
    findings: List[Finding] = []
    if surface is None:
        if inventory:
            findings.append(
                Finding(
                    path=ctx.faults_path,
                    line=1,
                    code="TSA1004",
                    message=(
                        "faults.py has no _CRASH_SURFACE table: "
                        f"{len(inventory)} discovered commit-point "
                        "function(s) are unpinned from the kill-point op "
                        "classes"
                    ),
                    key="no-crash-surface",
                )
            )
        return findings
    pinned = {site: (op, line) for site, op, line in surface}
    for site, (relpath, line) in sorted(inventory.items()):
        if site not in pinned:
            findings.append(
                Finding(
                    path=relpath,
                    line=line,
                    code="TSA1004",
                    message=(
                        f"commit-point function `{site}` is not pinned in "
                        "faults.py _CRASH_SURFACE: chaos schedules cannot "
                        "prove a crash here is survivable — map it to a "
                        "kill-point op class (or declare it fail-open)"
                    ),
                    key=f"unpinned:{site}",
                )
            )
    for site, op, line in surface:
        if site not in inventory:
            findings.append(
                Finding(
                    path=ctx.faults_path,
                    line=line,
                    code="TSA1004",
                    message=(
                        f"_CRASH_SURFACE entry `{site}` matches no "
                        "discovered commit-point function (renamed or "
                        "removed?) — stale entries hide real drift"
                    ),
                    key=f"stale:{site}",
                )
            )
        if op not in ops:
            findings.append(
                Finding(
                    path=ctx.faults_path,
                    line=line,
                    code="TSA1004",
                    message=(
                        f"_CRASH_SURFACE pins `{site}` to op class "
                        f"`{op}`, which is not in _OPS (nor `fail-open`): "
                        "no kill-point rule can ever reach it"
                    ),
                    key=f"badop:{site}:{op}",
                )
            )
    return findings


# --------------------------------------------------------------------- run


def run(ctx: AnalysisContext) -> List[Finding]:
    findings: List[Finding] = []
    for relpath in ctx.lib_files:
        findings.extend(_tsa1001(ctx, relpath))
        findings.extend(_tsa1002(ctx, relpath))
        findings.extend(_tsa1003(ctx, relpath))
    findings.extend(_tsa1004(ctx))
    return findings
