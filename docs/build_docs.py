#!/usr/bin/env python
"""Buildable docs pipeline (analogue of the reference's sphinx build +
``build_docs.yaml`` publish, ``/root/reference/docs/source`` — the docs
here are markdown, so the build renders them to HTML and, more importantly,
**checks them**):

- every ```python fenced block must parse (``compile(..., "exec")``) —
  catches snippet typos/indentation the way sphinx doctest syntax does;
- every relative link/file reference of the form ``[..](path)`` must exist;
- renders ``docs/*.md`` + the READMEs into ``docs/build/html/`` with
  python-markdown when available (CI installs it; the checks above run
  with zero dependencies either way).

    python docs/build_docs.py            # check + render
    python docs/build_docs.py --check    # check only (no output tree)
"""

from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC_SOURCES = [
    "README.md",
    "docs/getting_started.md",
    "docs/api_reference.md",
    "docs/utilities.md",
    "docs/observability.md",
    "docs/performance.md",
    "docs/robustness.md",
    "docs/lifecycle.md",
    "docs/static-analysis.md",
]

_FENCE_RE = re.compile(r"```(\w*)\n(.*?)```", re.DOTALL)
_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)]+)\)")


def check_snippets(relpath: str, text: str) -> list[str]:
    problems = []
    for i, m in enumerate(_FENCE_RE.finditer(text)):
        lang, body = m.group(1), m.group(2)
        if lang != "python":
            continue
        lineno = text[: m.start()].count("\n") + 2
        try:
            compile(body, f"{relpath}:snippet{i}", "exec")
        except SyntaxError as e:
            problems.append(
                f"{relpath}:{lineno}: python snippet does not parse: {e.msg} "
                f"(snippet line {e.lineno})"
            )
    return problems


def check_links(relpath: str, text: str) -> list[str]:
    problems = []
    base = os.path.dirname(os.path.join(ROOT, relpath))
    for m in _LINK_RE.finditer(text):
        # Validate the file part of `path#anchor` links too.
        target = m.group(1).strip().partition("#")[0]
        if not target or re.match(r"^[a-z]+://", target) or target.startswith("mailto:"):
            continue
        if not os.path.exists(os.path.normpath(os.path.join(base, target))):
            lineno = text[: m.start()].count("\n") + 1
            problems.append(f"{relpath}:{lineno}: broken relative link: {target}")
    return problems


def render(relpath: str, text: str, out_dir: str) -> None:
    try:
        import markdown
    except ImportError:
        return  # checks already ran; rendering is CI's job
    html = markdown.markdown(text, extensions=["tables", "fenced_code"])
    name = relpath.replace("/", "_").replace(".md", ".html")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        f.write(
            "<!DOCTYPE html><html><head><meta charset='utf-8'>"
            f"<title>{relpath}</title></head><body>\n{html}\n</body></html>\n"
        )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--check", action="store_true", help="check only")
    args = parser.parse_args()

    out_dir = os.path.join(ROOT, "docs", "build", "html")
    problems: list[str] = []
    for relpath in DOC_SOURCES:
        with open(os.path.join(ROOT, relpath), encoding="utf-8") as f:
            text = f.read()
        problems += check_snippets(relpath, text)
        problems += check_links(relpath, text)
        if not args.check:
            render(relpath, text, out_dir)
    if problems:
        print("\n".join(problems))
        print(f"\n{len(problems)} docs problem(s)")
        sys.exit(1)
    print(f"docs OK ({len(DOC_SOURCES)} sources)", end="")
    print("" if args.check else f"; rendered to {os.path.relpath(out_dir, ROOT)}")


if __name__ == "__main__":
    main()
