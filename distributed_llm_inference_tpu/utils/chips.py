"""One process for each chip: what a launcher does before it spawns children.

A TPU chip belongs to one process at a time, and a process that starts JAX
with no instructions asks for every chip of the host — so N engine servers
(serving/router.py --spawn) or N stage processes (serving/stage_runtime.py)
spawned with one shared environment fight over the same chips: the second
one dies at start-up. The launcher itself never touches JAX (it would hold
the chips its children need), so it counts the host's chips from the device
files the TPU runtime opens, and gives child i the runtime's per-process
visibility settings for chip i. More children than chips is refused before
anything is spawned.

On a host with no TPU, or when the children are held to the CPU
(JAX_PLATFORMS=cpu — the test suites), none of this applies and the
environment passes through untouched.
"""

from __future__ import annotations

import glob
import os
import tempfile
from typing import Optional


def local_chip_count() -> int:
    """TPU chips on this host, without initialising JAX: the accelerator
    device files (/dev/accel* on v4 and older, /dev/vfio/<n> on v5e+)."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def wants_chip(env) -> bool:
    """Would a JAX process started with `env` take a TPU here?"""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return local_chip_count() > 0


def check_chip_budget(n_children: int, env: Optional[dict] = None) -> None:
    """Refuse, loudly and before anything is spawned, to start more
    chip-holding children than this host has chips."""
    if not wants_chip(os.environ if env is None else env):
        return
    chips = local_chip_count()
    if n_children > chips:
        raise SystemExit(
            f"asked to spawn {n_children} processes that each need a TPU "
            f"chip, but this host has {chips}: a chip belongs to one "
            f"process at a time. Spawn at most {chips}, or hold the "
            f"children to the CPU with JAX_PLATFORMS=cpu."
        )


def child_env(env: Optional[dict], index: int) -> dict:
    """The environment of chip-holding child number `index`: on a TPU host
    it sees exactly chip `index` (the TPU runtime's per-process chip
    visibility — a one-chip process of its own, so several may load the
    runtime at once); anywhere else a plain copy of `env`."""
    out = dict(os.environ if env is None else env)
    if wants_chip(out):
        out["TPU_VISIBLE_CHIPS"] = str(index)
        out["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        out["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return out


def child_log(name: str):
    """Open (append) the file a spawned child's stdout/stderr go to, so a
    child that dies during start-up leaves its reason where the operator
    can read it. One directory per launcher process under the temp dir."""
    d = os.path.join(tempfile.gettempdir(), f"dli-children-{os.getpid()}")
    os.makedirs(d, exist_ok=True)
    return open(os.path.join(d, f"{name}.log"), "ab")


def log_tail(log_file, n_bytes: int = 4000) -> str:
    """Where a child's output went and how it ends, as a clause for the
    launcher's error message."""
    log_file.flush()
    with open(log_file.name, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - n_bytes))
        tail = f.read().decode("utf-8", errors="replace")
    return f"its output ({log_file.name}) ends:\n{tail}"
