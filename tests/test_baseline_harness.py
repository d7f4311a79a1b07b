"""The chip smoke stays a chip check: it cannot pass off the chip."""

import os
import subprocess
import sys


def _run_off_chip(script, *args):
    """Run a root-level script with JAX held to the CPU, as this sandbox
    and CI hold it; returns (returncode, stdout lines)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, script), *args],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    return proc.returncode, [l for l in proc.stdout.splitlines() if l.strip()]


def test_chip_smoke_cannot_pass_off_the_chip():
    """The guard that the smoke is a CHIP check: with no TPU its server
    child (started with JAX_PLATFORMS=tpu whatever the parent's setting)
    fails to start, the script exits non-zero, and no `ok: true` line is
    printed."""
    rc, lines = _run_off_chip("chip_smoke.py", "--model", "test-llama-tiny")
    assert rc != 0
    assert lines, "the smoke prints what it is about to start"
    assert '"ok": true' not in lines[-1]
    assert not any(l.lstrip().startswith('{"ok"') for l in lines)
