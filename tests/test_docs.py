"""Docs are executable: every quickstart block marked ``bash verify`` runs
verbatim (the local path of the user journey), ``python verify-write:<f>``
blocks are materialized as the files the commands expect, and the docs
build check (generated tables + links) passes.

Reference analog: torchx gates its docs with doctest + sphinx CI; here the
quickstart IS the test fixture, so the first page a user reads cannot rot.
"""

from __future__ import annotations

import re
import shlex
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
QUICKSTART = REPO / "docs" / "quickstart.md"

FENCE_RE = re.compile(
    r"^```(\w+) (verify[^\n`]*)\n(.*?)^```", re.M | re.S
)


def quickstart_blocks() -> list[tuple[str, str, str]]:
    """[(lang, marker, body)] in document order."""
    return [
        (m.group(1), m.group(2), m.group(3))
        for m in FENCE_RE.finditer(QUICKSTART.read_text())
    ]


def test_quickstart_has_verified_blocks():
    blocks = quickstart_blocks()
    langs = [lang for lang, _, _ in blocks]
    assert langs.count("bash") >= 2, blocks
    assert any(marker.startswith("verify-write:") for _, marker, _ in blocks)


@pytest.mark.integ
def test_quickstart_local_path_executes(tmp_path, one_local_gang):
    """Run the quickstart's CI-verified journey end to end in a scratch
    dir: write train.py exactly as documented, then execute every
    documented command and require success (the spmd run must actually
    form the 2x2 mesh)."""
    import os

    # redirect HOME so subprocesses' per-user registries (~/.tpx_local_apps
    # etc.) land in the scratch dir, not the developer's real home
    # ... and the checkout on PYTHONPATH: the commands run in the scratch
    # dir, where `-m torchx_tpu.cli.main` finds the package no other way
    path = os.pathsep.join(filter(None, [str(REPO), os.environ.get("PYTHONPATH")]))
    env = {**os.environ, "HOME": str(tmp_path), "PYTHONPATH": path}
    outputs: dict[str, str] = {}
    for lang, marker, body in quickstart_blocks():
        if lang == "python" and marker.startswith("verify-write:"):
            (tmp_path / marker.split(":", 1)[1]).write_text(body)
            continue
        assert lang == "bash" and marker == "verify", (lang, marker)
        for line in body.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            assert line.startswith("tpx "), f"unexpected quickstart cmd: {line}"
            argv = [sys.executable, "-m", "torchx_tpu.cli.main"] + shlex.split(
                line
            )[1:]
            proc = subprocess.run(
                argv,
                cwd=tmp_path,
                env=env,
                capture_output=True,
                text=True,
                timeout=300,
            )
            assert proc.returncode == 0, (
                f"quickstart cmd failed: {line}\n"
                f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
            )
            outputs[line] = proc.stdout + proc.stderr

    mesh_runs = [
        out for cmd, out in outputs.items() if "-j 2x2" in cmd
    ]
    assert mesh_runs and "SUCCEEDED" in mesh_runs[0]


def test_docs_build_check_passes():
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_docs.py")],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_every_diagnostic_code_documented():
    """Every TPX diagnostic code the analyzers can emit has a row in the
    torchx_tpu/analyze docstring table (the one gen_api_docs renders into
    docs/api/analyze.md), and the table carries no dead rows."""
    import torchx_tpu.analyze as analyze_pkg

    code_re = re.compile(r"TPX\d{3}")
    emitted: set[str] = set()
    for src in (
        REPO / "torchx_tpu" / "analyze" / "rules.py",
        REPO / "torchx_tpu" / "analyze" / "explain.py",
        REPO / "torchx_tpu" / "specs" / "file_linter.py",
        REPO / "torchx_tpu" / "cli" / "cmd_lint.py",
        # the selfcheck pass engine emits the TPX9xx whole-program codes
        *sorted((REPO / "torchx_tpu" / "analyze" / "selfcheck").glob("*.py")),
    ):
        emitted |= set(code_re.findall(src.read_text()))
    documented = {
        m.group(0)
        for line in (analyze_pkg.__doc__ or "").splitlines()
        if line.startswith("| TPX")
        for m in [code_re.search(line)]
        if m
    }
    assert emitted - documented == set(), (
        f"codes emitted but missing from the analyze docstring table:"
        f" {sorted(emitted - documented)}"
    )
    assert documented - emitted == set(), (
        f"documented codes nothing emits: {sorted(documented - emitted)}"
    )
