#!/usr/bin/env python
"""Self-contained lint gate (analogue of the reference's pre-commit hook,
``/root/reference/.github/workflows/pre_commit.yaml``) with zero
third-party dependencies, so the exact same check runs in CI and on any dev
box:

- every Python file must parse (syntax gate);
- unused imports (AST-walked; ``# noqa`` on the import line suppresses,
  ``__init__.py`` re-export lists are exempt);
- no tabs in indentation, no trailing whitespace, files end with a newline;
- the checkpoint-invariant static analyzer (``dev/analyze``: async-safety,
  task/future leaks, knob/telemetry drift, manifest schema, flow-sensitive
  resource balance, cross-thread mutation, fault-injection coverage,
  collective discipline — see ``docs/static-analysis.md``) over the
  library package.

    python dev/lint.py            # lint + analyze the repo
    python dev/lint.py FILES...   # lint specific files (analyzer runs too)
    python dev/lint.py --fix      # auto-fix trailing whitespace / missing
                                  # final newlines, then lint
"""

from __future__ import annotations

import ast
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_DIRS = ("torchsnapshot_tpu", "tests", "examples", "dev", "docs")
LINT_FILES = ("chip_smoke.py", "__graft_entry__.py")


def iter_targets(argv: list[str]) -> list[str]:
    if argv:
        return argv
    out = []
    for d in LINT_DIRS:
        for dirpath, _, filenames in os.walk(os.path.join(ROOT, d)):
            out.extend(
                os.path.join(dirpath, f) for f in filenames if f.endswith(".py")
            )
    out.extend(os.path.join(ROOT, f) for f in LINT_FILES)
    return sorted(p for p in out if os.path.exists(p))


def _used_names(tree: ast.AST) -> set:
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            # a.b.c -> record the root name
            n = node
            while isinstance(n, ast.Attribute):
                n = n.value
            if isinstance(n, ast.Name):
                used.add(n.id)
    # Names referenced only in string annotations / docstring doctests are
    # not resolvable statically; __all__ strings count as uses.
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            used.add(node.value)
    return used


def unused_imports(tree: ast.AST, source_lines: list[str]) -> list:
    used = _used_names(tree)
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        if isinstance(node, ast.ImportFrom) and node.module == "__future__":
            continue
        line = source_lines[node.lineno - 1]
        if "noqa" in line:
            continue
        for alias in node.names:
            if alias.name == "*":
                continue
            bound = alias.asname or alias.name.split(".")[0]
            if bound not in used:
                problems.append((node.lineno, f"unused import: {bound}"))
    return problems


def lint_file(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    problems = []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [(e.lineno or 0, f"syntax error: {e.msg}")]
    lines = source.split("\n")
    if not os.path.basename(path) == "__init__.py":
        problems.extend(unused_imports(tree, lines))
    for i, line in enumerate(lines, 1):
        stripped = line.rstrip("\n")
        if stripped != stripped.rstrip():
            problems.append((i, "trailing whitespace"))
        indent = stripped[: len(stripped) - len(stripped.lstrip())]
        if "\t" in indent:
            problems.append((i, "tab in indentation"))
    if source and not source.endswith("\n"):
        problems.append((len(lines), "no newline at end of file"))
    return problems


def fix_file(path: str) -> bool:
    """Auto-remediate the mechanical problems: trailing whitespace and a
    missing final newline. Returns True when the file changed. Tabs in
    indentation are NOT auto-fixed (the right width is a judgment call)."""
    with open(path, encoding="utf-8") as f:
        source = f.read()
    if not source:
        return False
    fixed = "\n".join(line.rstrip() for line in source.split("\n"))
    if not fixed.endswith("\n"):
        fixed += "\n"
    if fixed == source:
        return False
    with open(path, "w", encoding="utf-8") as f:
        f.write(fixed)
    return True


def check_analyzer(paths: list) -> int:
    """The static-analysis gate (``python -m dev.analyze``): all ten
    passes (see dev/analyze/__init__.py). Subprocess so the analyzer's
    import path (repo root) never depends on how lint was invoked."""
    import subprocess

    cmd = [sys.executable, "-m", "dev.analyze", *paths]
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        return 1
    return 0


def main() -> None:
    argv = sys.argv[1:]
    fix = "--fix" in argv
    argv = [a for a in argv if a != "--fix"]
    failed = 0
    explicit_files = bool(argv)
    targets = iter_targets(argv)
    if fix:
        n_fixed = 0
        for path in targets:
            if fix_file(path):
                print(f"fixed: {os.path.relpath(path, ROOT)}")
                n_fixed += 1
        print(f"--fix: {n_fixed} file(s) rewritten")
    for path in targets:
        for lineno, msg in lint_file(path):
            print(f"{os.path.relpath(path, ROOT)}:{lineno}: {msg}")
            failed += 1
    if explicit_files:
        # Analyzer conventions apply to the library package; lint-on-save of
        # a test or tool file shouldn't trip library-only gates.
        lib_paths = [
            p
            for p in targets
            if os.path.relpath(p, ROOT).startswith("torchsnapshot_tpu" + os.sep)
        ]
        if lib_paths:
            failed += check_analyzer(lib_paths)
    else:
        failed += check_analyzer([])
    if failed:
        print(f"\n{failed} lint problem(s)")
        sys.exit(1)
    print("lint clean")


if __name__ == "__main__":
    main()
