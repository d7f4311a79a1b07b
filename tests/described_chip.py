"""What the files that compile for a TPU v5e that is DESCRIBED, not attached
(on-chip-measurement guide, section 2) share: the fixtures, abstract
operands placed on the described chip, and the readers of a compiled
module's text.

Interpret mode hides what the chip's compiler refuses — every int8-KV kernel
variant passed the interpret-mode suite and was refused by the TPU lowering
(block-shape tiling of the scale operands) until PR 21. These compiles cost
no chip time and run in the test's own process; nothing executes, so they say
nothing about results or speed.

Rules the files keep: the topology is described inside a module-scoped,
non-autouse fixture that skips when it cannot be described — never at
import, never in a skipif or parametrize argument, never in conftest.py.
Kernels take interpret=False explicitly; the whole-model steps steer
`resolve_interpret` (which would see the CPU backend and lower the
interpreter) with DLI_PALLAS_INTERPRET=0 around the lowering
(tests/dense_equal.programs does it itself), not through a new option of
the program. The files, by what shares a compile (the suite runs under
xdist's `loadfile`: a file is one worker's, so no file may be the run):
tests/test_chip_compile.py the kernels alone, tests/test_chip_tinyllama.py
chip_smoke.py's fleet, tests/test_cell_programs_*.py the benchmark's
configurations, two a file (tests/cell_program_checks.py).
"""

import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without a chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def spec(sharding):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return make


def placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# What a device trace calls the fleet's two step programs, their two
# attention kernels and the routed experts' grouped product: the benchmark's
# configurations name these strings under `serving.trace`
# (cellbench/configs/*.json), the per-layer metrics find their events by
# them, and a rename must fail in these files, not null them.
STEP_MODULES = {"mixed_step_ragged", "decode_slots_paged"}
ATTENTION_KERNELS = {"ragged_paged_attend", "paged_flash_attend"}
EXPERT_KERNELS = {"routed_expert_matmul"}
DENSE_SCOPES = ("embed", "attn", "ffn", "head", "sample")
ROUTED_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")


def module_name(hlo_text):
    return hlo_text.split("HloModule ", 1)[1].split(",", 1)[0].split()[0]


def custom_call_names(hlo_text):
    """Instruction names of the compiled module's custom calls, as a device
    trace's `XLA Ops` line shows them (`%paged_flash_attend.3`)."""
    return set(re.findall(r"%([\w.\-]+) = [^\n]*custom-call\(", hlo_text))


def assert_scopes(hlo_text, module, labels):
    """Every label of the family's vocabulary (utils/tracing.STEP_SCOPES)
    labels at least one instruction of the compiled step program: what
    `program_scopes.json` is made from when a profiler session ends
    (ISSUE 38), and the six per-layer metrics read."""
    from distributed_llm_inference_tpu.utils import tracing

    assert set(labels) <= set(tracing.STEP_SCOPES)
    (name, insts), = tracing.scope_map(hlo_text).items()
    assert module in name
    held = {label for v in insts.values() for label in v["scope"]}
    assert held >= set(labels), (module, sorted(set(labels) - held))
    # a kernel is an instruction of its block
    for inst, v in insts.items():
        if "paged_attend" in inst or "paged_flash_attend" in inst:
            assert v["scope"][:1] == ["attn"], (inst, v)
        if "routed_expert_matmul" in inst:
            assert v["scope"] == ["moe_experts"], (inst, v)
