"""Smoke test: the 5-config BASELINE harness stays runnable in CI."""

import json
import os
import io
import contextlib
import subprocess
import sys

import pytest


@pytest.mark.slow  # re-tiered round 5: compiles all five config shapes
def test_harness_runs_each_config_shape(capsys):
    sys.path.insert(0, "benchmarks")
    from benchmarks.run_baseline_configs import main

    # conftest already forces the 8-device CPU mesh; run the two cheapest
    # configs end to end (single-device + 2-stage pipeline)
    main(["--scale", "tiny", "--configs", "1,2", "--steps", "4"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 2
    for i, line in enumerate(lines):
        rec = json.loads(line)
        assert rec["config"] == i + 1
        assert rec["tokens_per_sec"] > 0
        assert rec["ttft_s"] >= 0
        assert rec["platform"] == "cpu"


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


def test_bench_needs_a_tpu():
    """bench.py is one process that measures a TPU: off the chip it exits
    non-zero before building a model, and emits no result line."""
    rc, lines = _run_off_chip("bench.py")
    assert rc != 0
    assert not any(l.lstrip().startswith("{") for l in lines)
