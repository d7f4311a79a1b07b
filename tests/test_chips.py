"""What launchers do before they start anything. One process for each chip
(utils/chips.py): per-child chip visibility on a TPU host, the refusal
before spawning, and readable start-up failures — the TPU host is simulated
by its chip count, nothing here starts a TPU. And the compile cache's one
rule (utils/compile_cache.py)."""

import subprocess

import pytest

from distributed_llm_inference_tpu.serving import router as R
from distributed_llm_inference_tpu.utils import chips

CPU_ENV = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin"}
TPU_ENV = {"PATH": "/usr/bin"}  # JAX_PLATFORMS unset: JAX would take the TPU


@pytest.fixture
def four_chip_host(monkeypatch):
    monkeypatch.setattr(chips, "local_chip_count", lambda: 4)


@pytest.mark.parametrize("env", [CPU_ENV, TPU_ENV], ids=["cpu", "unset"])
def test_child_env_passes_through_off_a_tpu_host(monkeypatch, env):
    monkeypatch.setattr(chips, "local_chip_count", lambda: 0)
    assert chips.child_env(env, 3) == env
    chips.check_chip_budget(64, env)  # nothing to refuse


def test_child_env_gives_each_child_its_own_chip(four_chip_host):
    envs = [chips.child_env(TPU_ENV, i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "TPU_VISIBLE_CHIPS" not in TPU_ENV  # the caller's dict is untouched
    # children held to the CPU need no chip, on a TPU host too
    assert chips.child_env(CPU_ENV, 2) == CPU_ENV


def test_more_children_than_chips_is_refused_before_any_spawn(
    four_chip_host, monkeypatch
):
    def no_spawn(*a, **k):
        raise AssertionError("spawned a child before checking the chips")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    with pytest.raises(SystemExit, match="5 processes.*this host has 4"):
        R.spawn_replicas(5, ["--model", "test-llama-tiny"], env=TPU_ENV)
    with pytest.raises(SystemExit, match="this host has 4"):
        # groups count together: chips 3.. are already spoken for
        R.spawn_replicas(
            2, ["--model", "test-llama-tiny"], env=TPU_ENV, first_chip=3
        )
    chips.check_chip_budget(4, TPU_ENV)
    chips.check_chip_budget(9, CPU_ENV)


def test_startup_failure_of_a_child_is_readable(monkeypatch, tmp_path):
    """A replica that dies while starting leaves its reason in the
    launcher's error, not in /dev/null."""
    import os

    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    with pytest.raises(SystemExit) as err:
        R.spawn_replicas(
            1, ["--model", "no-such-model"],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), ready_deadline_s=120,
        )
    msg = str(err.value)
    assert "exited rc=" in msg and "no-such-model" in msg
    assert str(tmp_path) in msg  # where the whole log is


# -- the compile cache's one rule (utils/compile_cache.py) --------------------

def _recorded_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_compile_cache_placed_from_outside_sets_no_directory(monkeypatch):
    from distributed_llm_inference_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    calls = _recorded_updates(monkeypatch)
    assert compile_cache.enable() == "/some/dir"
    assert calls == [("jax_persistent_cache_min_compile_time_secs", 0.0)]


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import os

    from distributed_llm_inference_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    calls = _recorded_updates(monkeypatch)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.enable() == os.path.join(root, ".xla_cache")
    assert calls == [
        ("jax_compilation_cache_dir", os.path.join(root, ".xla_cache")),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ]
