"""Every example runs green in --smoke mode (the examples are part of the
product surface — the reference ships dl4j-examples; these mirror it)."""

import pathlib
import subprocess
import sys

import pytest

EXAMPLES = sorted(
    p for p in (pathlib.Path(__file__).parent.parent / "examples").glob(
        "*.py") if p.name != "_common.py")


@pytest.mark.parametrize("script", EXAMPLES, ids=lambda p: p.stem)
def test_example_smoke(script):
    proc = subprocess.run(
        [sys.executable, str(script), "--smoke"],
        capture_output=True, text=True, timeout=900,
        cwd=script.parent)
    assert proc.returncode == 0, (
        f"{script.name} failed:\n{proc.stdout[-2000:]}\n"
        f"{proc.stderr[-2000:]}")
    assert "OK" in proc.stdout or "SKIP" in proc.stdout, proc.stdout[-500:]
