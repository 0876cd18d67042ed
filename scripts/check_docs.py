#!/usr/bin/env python
"""Docs build check: the CI gate for docs/.

1. scheduler pages are in sync with the live runopts schemas
   (scripts/gen_scheduler_docs.py --check);
2. every relative markdown link in docs/ resolves to a real file;
3. every page renders with python-markdown (catches broken fences/tables).

Exit 0 = docs are buildable and current.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOCS = REPO / "docs"

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(#[^)]*)?\)")


def check_generated() -> list[str]:
    errors = []
    # the generators import the live package: this checkout's, wherever
    # the check was started from and whether or not it is installed
    path = os.pathsep.join(filter(None, [str(REPO), os.environ.get("PYTHONPATH")]))
    for script in ("gen_scheduler_docs.py", "gen_api_docs.py"):
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / script), "--check"],
            capture_output=True,
            text=True,
            env={**os.environ, "PYTHONPATH": path},
        )
        if proc.returncode != 0:
            errors.append(
                f"{script} --check failed (stale pages or public symbols"
                f" missing docstrings):\n{proc.stderr.strip()}"
            )
    return errors


def check_links() -> list[str]:
    errors = []
    pages = sorted(DOCS.rglob("*.md")) + [REPO / "README.md"]
    for page in pages:
        for m in LINK_RE.finditer(page.read_text()):
            target = m.group(1)
            if "://" in target or target.startswith("mailto:"):
                continue
            resolved = (page.parent / target).resolve()
            if not resolved.exists():
                errors.append(
                    f"{page.relative_to(REPO)}: broken link -> {target}"
                )
    return errors


def check_render() -> list[str]:
    try:
        import markdown
    except ImportError:
        return []  # renderer not available in this env; links+drift still gate
    errors = []
    for page in sorted(DOCS.rglob("*.md")):
        try:
            markdown.markdown(
                page.read_text(), extensions=["tables", "fenced_code"]
            )
        except Exception as e:  # noqa: BLE001 - any render error fails CI
            errors.append(f"{page.relative_to(REPO)}: render error: {e}")
    return errors


def main() -> int:
    errors = check_generated() + check_links() + check_render()
    for e in errors:
        print(f"DOCS ERROR: {e}", file=sys.stderr)
    pages = len(list(DOCS.rglob("*.md")))
    if not errors:
        print(f"docs ok: {pages} pages, links resolve, runopts tables current")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
