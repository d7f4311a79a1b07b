"""Test harness: force the CPU backend with 8 virtual devices so N-stage
pipeline tests run on any host with no TPU (SURVEY.md §4). Must run before
any test module initializes a JAX backend."""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
# Run every Pallas kernel (flash / paged decode / ragged) in interpret
# mode regardless of backend (ops/flash_attention.resolve_interpret reads
# this), so tier-1 exercises the kernels' exact math on CPU — the ragged
# kernel's bit-exactness suite (tests/test_ragged_attention.py) depends
# on it. Set to "0" to force real Mosaic lowering on a TPU host.
os.environ.setdefault("DLI_PALLAS_INTERPRET", "1")

import jax

jax.config.update("jax_default_matmul_precision", "highest")

import pytest


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
