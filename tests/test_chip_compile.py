"""Compile the main path's kernels and whole step programs for a TPU v5e
that is DESCRIBED, not attached (on-chip-measurement guide, section 2).

Interpret mode hides what the chip's compiler refuses — every int8-KV kernel
variant passed the interpret-mode suite and was refused by the TPU lowering
(block-shape tiling of the scale operands) until PR 21. These compiles cost
no chip time and run in the test's own process; nothing executes, so they say
nothing about results or speed.

Rules this file keeps (the driver runs the suite under several xdist workers,
and only one process at a time may load the TPU library): the topology is
described inside a module-scoped, non-autouse fixture that skips when it
cannot be described — never at import, never in a skipif or parametrize
argument, never in conftest.py — and ALL such tests live in this one file.
Kernels take interpret=False explicitly; the whole-model steps steer
`resolve_interpret` (which would see the CPU backend and lower the
interpreter) with DLI_PALLAS_INTERPRET=0 in the test, not through a new
option of the program.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llm_inference_tpu.config import resolve_attn_impl
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as EP
from distributed_llm_inference_tpu.engine.scheduler import step_width
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models.registry import get_model_config
from distributed_llm_inference_tpu.ops import quant as Q
from distributed_llm_inference_tpu.ops.flash_attention import flash_attend
from distributed_llm_inference_tpu.ops.kv_quant import KVQuant
from distributed_llm_inference_tpu.ops.paged_attention import (
    paged_flash_attend,
    ragged_paged_attend,
)

from paged_walk_cases import CELL_CONFIGS, cell_pool

# TinyLlama-1.1B widths: 32 query heads over 4 kv heads, head_dim 64
H, KV, DH = 32, 4, 64
POOL_BLOCKS = 3072  # chip_smoke.py's pool: >= 1 GiB of bf16 KV at bs 16
SLOTS = 8


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


def _spec(sharding):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return make


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _kv(S, shape, quant):
    """A cache/pool operand [..., tokens, DH]: bf16, or the int8 KVQuant
    leaf pair (int8 data + fp32 per-(token, head) scales)."""
    if quant:
        return KVQuant(S(shape, jnp.int8), S(shape[:-1], jnp.float32))
    return S(shape, jnp.bfloat16)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("T,seq", [(128, 512), (512, 2048)])
def test_flash_attend_compiles(one_chip, no_persistent_cache, T, seq, quant):
    S = _spec(one_chip)
    kv = _kv(S, (1, KV, seq, DH), quant)
    text = _compile(
        functools.partial(flash_attend, interpret=False),
        S((1, T, H, DH), jnp.bfloat16), kv, kv, S((), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("bs", [16, 32, 128])
def test_paged_flash_attend_compiles(one_chip, no_persistent_cache, bs, quant):
    S = _spec(one_chip)
    pool = _kv(S, (POOL_BLOCKS, KV, bs, DH), quant)
    text = _compile(
        functools.partial(paged_flash_attend, interpret=False),
        S((SLOTS, 1, H, DH), jnp.bfloat16), pool, pool,
        S((SLOTS, 2048 // bs), jnp.int32), S((SLOTS,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("bs,tq", [(16, 8), (16, 16), (32, 32), (128, 128)])
def test_ragged_paged_attend_compiles(one_chip, no_persistent_cache, bs, tq,
                                      quant):
    """(16, 8) is the serving shape (continuous.py's 8-row query tile over
    chip_smoke.py's 16-token blocks); the rest are bs == tq."""
    S = _spec(one_chip)
    pool = _kv(S, (POOL_BLOCKS, KV, bs, DH), quant)
    tiles = 16
    text = _compile(
        functools.partial(ragged_paged_attend, interpret=False),
        S((tiles * tq, H, DH), jnp.bfloat16), pool, pool,
        S((SLOTS, 2048 // bs), jnp.int32), S((tiles, 4), jnp.int32),
    )
    assert "tpu_custom_call" in text


# The benchmark's two configurations as their cells serve them
# (cellbench/configs/*.json): slots, query heads, KV heads, head dim, block
# size, table width, pool blocks, window.
CELL_SHAPES = {
    "olmo2-7b-16l": (12, 32, 32, 128, 128, 16, 61, None),
    "mistral-7b-16l": (16, 32, 8, 128, 128, 50, 271, 4096),
}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_paged_flash_attend_compiles_at_cell_shapes(
    one_chip, no_persistent_cache, cell, quant
):
    """The pure-decode chunk's kernel, with the slots' active mask."""
    slots, h, kv, dh, bs, mb, blocks, window = CELL_SHAPES[cell]
    S = _spec(one_chip)
    pool = _kv(S, (blocks, kv, bs, dh), quant)
    text = _compile(
        functools.partial(paged_flash_attend, interpret=False, window=window),
        S((slots, 1, h, dh), jnp.bfloat16), pool, pool,
        S((slots, mb), jnp.int32), S((slots,), jnp.int32), None,
        S((slots,), jnp.bool_),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_ragged_paged_attend_compiles_at_cell_shapes(
    one_chip, no_persistent_cache, cell, quant
):
    """The mixed step's kernel: `step_width` flat tokens (a dense model's
    128, or one tile above the fleet) in query tiles of 8 (16 tiles for
    olmo2's 12 slots, 17 for mistral's 16)."""
    slots, h, kv, dh, bs, mb, blocks, window = CELL_SHAPES[cell]
    S = _spec(one_chip)
    pool = _kv(S, (blocks, kv, bs, dh), quant)
    tq = 8
    tiles = step_width(get_model_config(CELL_PROGRAMS[cell][0]), slots, tq) // tq
    assert tiles == {"olmo2-7b-16l": 16, "mistral-7b-16l": 17}[cell]
    text = _compile(
        functools.partial(ragged_paged_attend, interpret=False, window=window),
        S((tiles * tq, h, dh), jnp.bfloat16), pool, pool,
        S((slots, mb), jnp.int32), S((tiles, 4), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_q4_matmul_rows_compiles(one_chip, no_persistent_cache):
    """The int4 weight kernel at TinyLlama's gate/up projection shape."""
    w = _placed(
        jax.eval_shape(
            lambda: Q.quantize_tensor4(jnp.zeros((2048, 5632), jnp.float32))
        ),
        one_chip,
    )
    text = _compile(
        functools.partial(Q.q4_matmul_rows, interpret=False),
        _spec(one_chip)((SLOTS, 2048), jnp.float32), w,
    )
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def tinyllama(one_chip):
    """(cfg, params) of the whole published tinyllama-1.1b in bf16 with the
    Pallas attention path selected, as shapes placed on the described chip."""
    cfg = resolve_attn_impl(
        get_model_config("tinyllama-1.1b").replace(dtype="bfloat16"), "pallas"
    )
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, _placed(params, one_chip)


def test_tinyllama_prefill_step_compiles_with_kernel(
    one_chip, no_persistent_cache, tinyllama, monkeypatch
):
    """One whole prefill (T 128) of the engine's own program: without the
    env steer the compile contains no kernel at all — resolve_interpret
    sees the CPU backend and lowers the interpreter."""
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, params = tinyllama
    S = _spec(one_chip)
    place = functools.partial(_placed, sharding=one_chip)
    cache = place(jax.eval_shape(
        lambda: M.init_kv_cache(cfg, 1, max_seq=cfg.max_seq_len)
    ))
    i32 = S((), jnp.int32)
    compiled = G.prefill.lower(
        cfg, params, S((1, 128), jnp.int32), i32, cache,
        place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
        place(jax.eval_shape(lambda: G.default_sampling(greedy=True))),
        None, i32, None, None,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _tinyllama_fleet(tinyllama, one_chip):
    """(cfg, params, state, sparams, pool, table, key) of chip_smoke.py's
    paged fleet, as shapes placed on the described chip."""
    cfg, params = tinyllama
    place = functools.partial(_placed, sharding=one_chip)
    state, sparams = place(
        jax.eval_shape(lambda: G.init_slots(SLOTS, cfg.vocab_size))
    )
    pool = place(jax.eval_shape(lambda: EP.init_pool(cfg, POOL_BLOCKS, 16)))
    table = _spec(one_chip)((SLOTS, 2048 // 16), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    return cfg, params, state, sparams, pool, table, key


# Head dim 64 is not whole 128-lane tiles, so the kernel reads a padded copy
# of a layer's slice of each pool leaf (ops/paged_attention.writes_in_place)
PADDED_SLICE = POOL_BLOCKS * KV * 16 * 128 * 2


def test_tinyllama_paged_decode_chunk_compiles_with_kernel(
    one_chip, no_persistent_cache, tinyllama, monkeypatch
):
    """The fleet's decode program as it is served: `decode_slots_paged` at
    `--continuous-chunk`'s default 16 steps over the block pool, whose
    attention is the paged kernel walking the table."""
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, params, state, sparams, pool, table, key = _tinyllama_fleet(
        tinyllama, one_chip
    )
    compiled = EP.decode_slots_paged.lower(
        cfg, params, state, pool, table, key, sparams, num_steps=16,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "jit_decode_slots_paged" in text
    # Around a chunk loop (the scan before PR 46, `steps_while_active`
    # since: 2.317 GB either way) the compiler carries this head dim's pool
    # in the kernel's padded layout: ONE relayout of both leaves at the
    # chunk's entry and exit, 22 layers x 2 leaves x 50 MB = 2.2 GB, in
    # place of a padded copy of a layer's slice at each of 16 x 22 layer
    # steps. Held here: that copy and one step's temporaries, never a
    # second one (a carry that is not written in place).
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < (2 * cfg.n_layers + 2) * PADDED_SLICE + 2**28, temps


def test_tinyllama_paged_decode_step_compiles_with_kernel(
    one_chip, no_persistent_cache, tinyllama, monkeypatch
):
    """One whole decode step (T 1 per slot), the body of the chunk's loop,
    compiled as a program of its own: what `decode_slots_paged(num_steps=1)`
    was while a chunk was a scan, which the compiler unrolled at length one.
    A loop whose trip count the device decides stays a loop at a bound of
    one, and its pool carry takes the chunk's relayout (the test above)."""
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, params, state, sparams, pool, table, key = _tinyllama_fleet(
        tinyllama, one_chip
    )

    def one_step(params, state, pool, table, key, sparams):
        logits, pool = EP._forward_step_paged(
            cfg, params, state.token[:, None], pool, table, state.pos,
            active=state.active,
        )
        return G.slot_step(cfg, state, sparams, logits, key), pool

    compiled = jax.jit(one_step, donate_argnums=(2,)).lower(
        params, state, pool, table, key, sparams,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the padded copy is of ONE layer's slice, as it was before the pool
    # became a carry (temporaries 1.41 GB then, with the scan's second pool;
    # 0.10 GB now): never a padded copy of the stacked pool inside a step
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 2 * PADDED_SLICE + 2**28, temps


# What a device trace calls the fleet's two step programs and their two
# attention kernels: the benchmark's configurations name these strings under
# `serving.trace` (cellbench/configs/*.json), five per-layer metrics find
# their events by them, and a rename must fail here, not null them.
STEP_MODULES = {"mixed_step_ragged", "decode_slots_paged"}
ATTENTION_KERNELS = {"ragged_paged_attend", "paged_flash_attend"}


def _module_name(hlo_text):
    return hlo_text.split("HloModule ", 1)[1].split(",", 1)[0].split()[0]


def _custom_call_names(hlo_text):
    """Instruction names of the compiled module's custom calls, as a device
    trace's `XLA Ops` line shows them (`%paged_flash_attend.3`)."""
    import re

    return set(re.findall(r"%([\w.\-]+) = [^\n]*custom-call\(", hlo_text))


def _assert_scopes(hlo_text, module, labels):
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


DENSE_SCOPES = ("embed", "attn", "ffn", "head", "sample")
ROUTED_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")


def test_step_programs_and_kernels_carry_the_names_a_trace_is_read_by(
    one_chip, no_persistent_cache, tinyllama, monkeypatch
):
    import glob
    import json
    import os

    import numpy as np

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, params = tinyllama
    S = _spec(one_chip)
    place = functools.partial(_placed, sharding=one_chip)
    state, sparams = place(
        jax.eval_shape(lambda: G.init_slots(SLOTS, cfg.vocab_size))
    )
    pool = place(jax.eval_shape(lambda: EP.init_pool(cfg, POOL_BLOCKS, 16)))
    table = S((SLOTS, 2048 // 16), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    chunk = EP.decode_slots_paged.lower(
        cfg, params, state, pool, table, key, sparams, num_steps=2,
    ).compile().as_text()
    # the mixed step as the scheduler launches it: a flat axis of
    # (slots + 1) tiles, decode positions derived on the device
    tile, width = 8, (SLOTS + 1) * 8
    entries = [(b, 0, 1, EP.RAGGED_DECODE) for b in range(SLOTS)]
    meta, tok_row, tok_pos, offsets, _ = EP.build_ragged_meta(
        entries, width=width, tile=tile
    )
    dev = EP.DeviceMeta(*(
        S(a.shape, a.dtype) for a in EP.build_device_meta(
            entries, offsets, SLOTS, width=width, tile=tile)
    ))
    arm = place(jax.eval_shape(
        lambda: EP.idle_mixed_arm(SLOTS, cfg.vocab_size)
    ))
    flat = lambda a: S(np.shape(a), np.asarray(a).dtype)  # noqa: E731
    mixed = EP.mixed_step_ragged.lower(
        cfg, params, S((width,), jnp.int32), flat(tok_row), flat(tok_pos),
        S((width,), jnp.bool_), flat(meta), pool, table, state, sparams, key,
        S((SLOTS,), jnp.int32), arm, dev=dev,
    ).compile().as_text()
    names = {
        "decode_slots_paged": (chunk, "paged_flash_attend"),
        "mixed_step_ragged": (mixed, "ragged_paged_attend"),
    }
    for module, (text, kernel) in names.items():
        assert module in _module_name(text), _module_name(text)
        calls = _custom_call_names(text)
        assert any(kernel in c for c in calls), (module, sorted(calls))
        _assert_scopes(text, module, DENSE_SCOPES)
    # and these are the strings the benchmark's configurations name
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = glob.glob(os.path.join(root, "cellbench", "configs", "*.json"))
    assert files
    for path in files:
        with open(path) as f:
            trace = json.load(f)["serving"]["trace"]
        assert set(trace["step_modules"]) == STEP_MODULES, path
        assert set(trace["attention_kernels"]) == ATTENTION_KERNELS, path


# -- the pool is a loop carry that the kernels write in place (ISSUE 29) --------
#
# The three configurations at their cells' sizes (cellbench/configs/*.json:
# model, layers, slots, pool blocks, context; 128-token blocks, query tiles
# of 8). Before ISSUE 29 each step program held a second pool as a temporary
# (2.56 / 2.97 GB for olmo2's 2.05 GB pool, 2.70 / 3.51 for mistral's 2.27,
# 2.549 / 2.302 for kanana's 2.007) and moved the pool about five times a
# step; what is left is weights relaid out once a launch.
CELL_PROGRAMS = {
    "olmo2-7b-16l": ("olmo2-7b", 16, 12, 61, 2048),
    "mistral-7b-16l": ("mistral-7b", 16, 16, 271, 6400),
    "kanana-2-30b-a3b-7l": ("kanana-2-30b-a3b", 7, 8, 1750, 32768),
}
CELL_WIDTHS = {"olmo2-7b-16l": 128, "mistral-7b-16l": 136,
               "kanana-2-30b-a3b-7l": 512, "trinity-large-ep8-5l": 512}
# configurations read from their benchmark file (registry entry, overrides,
# flags): a pool grouped by layer kind, two tables side by side, no verify
# rows (ISSUE 40)
CELL_FILES = ("trinity-large-ep8-5l",)
# instructions that make no buffer of their own, or are the kernels
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "custom-call"}


def _pool_sized_instructions(hlo_text, pool):
    """Instructions of a compiled module whose result has the shape of a
    pool leaf or of one layer's slice of it (`copy`, `dynamic-slice`,
    `dynamic-update-slice`, `scatter`, bare or as a fusion's root)."""
    import re

    shapes = set()
    for leaf in jax.tree.leaves(pool):
        if leaf.ndim == 5:
            dims = [str(d) for d in leaf.shape]
            shapes |= {",".join(dims), ",".join(dims[1:]),
                       ",".join(["1"] + dims[1:])}
    found = re.findall(
        r"%([\w.\-]+) = \w+\[([\d,]+)\]\{[^}]*\} ([\w\-]+)\(", hlo_text)
    return sorted(f"{op} {name} [{shape}]" for name, shape, op in found
                  if shape in shapes and op not in _NO_BUFFER)


def _projection_sized_instructions(hlo_text, layers):
    """Instructions of a compiled module that write out an attention
    projection's weights (ISSUE 39): a result with the shape of the stacked
    leaf `wq` / `wk` / `wv` / `wo` of `layers` (the parameters' tree; the
    latent family's two stacks each) or of one layer's slice of it, made by
    anything but a fusion that holds the dot itself. Where a dot reads its
    layer in place, the scan's `dynamic-slice` sits INSIDE the dot's fused
    computation and no such instruction exists; a `copy` of a stack (its
    relayout, once a launch) or a loop fusion around the slice (one layer's
    weights copied out a layer-step) is what this lists. Not listed: the
    compiler's own asynchronous prefetches (`copy-start` / `slice-start`
    and their `-done`: the same layout into another memory space,
    overlapped), and `w_kvb` / `w_kva` of the latent family, whose copies
    have other causes (PERF.md section 7)."""
    import re

    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(layers)[0]:
        if getattr(path[-1], "key", None) in ("wq", "wk", "wv", "wo") \
                and leaf.ndim == 3:
            dims = [str(d) for d in leaf.shape]
            shapes |= {",".join(dims), ",".join(["1"] + dims[1:])}
    assert shapes
    blocks = re.findall(r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}",
                        hlo_text, re.M | re.S)
    fused = {name for name, _ in blocks if "fused_computation" in name}
    with_dot = {name for name, body in blocks
                if re.search(r" (dot|convolution)\(", body)}
    assert fused and fused & with_dot
    found = []
    for name, body in blocks:
        if name in fused:
            continue  # (a fusion's inner instructions make no buffer)
        for inst, shape, op, rest in re.findall(
                r"%([\w.\-]+) = \w+\[([\d,]+)\]\{[^}]*\} ([\w\-]+)\(([^\n]*)",
                body):
            if shape not in shapes or op in _NO_BUFFER \
                    or op.endswith(("-start", "-done")):
                continue
            calls = re.search(r"calls=%([\w.\-]+)", rest)
            if op == "fusion" and calls and calls.group(1) in with_dot:
                continue
            found.append(f"{op} {inst} [{shape}]")
    return sorted(found)


@functools.cache  # (several tests of this file read them: a minute a compile)
def _cell_step_programs(one_chip, config):
    """(params, pool, {module: compiled}) of a CELL_PROGRAMS configuration:
    both step programs at the cell's sizes and depth. The caller has set
    DLI_PALLAS_INTERPRET=0."""
    import json
    import os

    import numpy as np

    grouped = config in CELL_FILES
    if grouped:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "cellbench", "configs", f"{config}.json")) as f:
            serving = json.load(f)["serving"]
        flags = serving["flags"]
        flag = lambda name: int(flags[flags.index(name) + 1])  # noqa: E731
        slots, blocks, context = (flag("--continuous"), flag("--kv-pool-blocks"),
                                  flag("--continuous-max-seq"))
        cfg = get_model_config(serving["base"]).replace(
            dtype="bfloat16", **serving["overrides"])
    else:
        model, layers, slots, blocks, context = CELL_PROGRAMS[config]
        cfg = get_model_config(model).replace(n_layers=layers, dtype="bfloat16")
    cfg = resolve_attn_impl(cfg, "pallas")
    S = _spec(one_chip)
    place = functools.partial(_placed, sharding=one_chip)
    params = place(jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    state, sparams = place(jax.eval_shape(lambda: G.init_slots(slots, cfg.vocab_size)))
    if grouped:
        width = step_width(cfg, slots, 8)
        budget = EP.window_row_budget(cfg.attn_window, width, 128)
        blocks = EP.group_blocks(cfg, blocks, budget, slots)
        assert blocks == (4608, 1152) and budget == 37
    pool = place(jax.eval_shape(lambda: EP.init_pool(cfg, blocks, 128)))
    table = S((slots, len(cfg.kv_groups) * (context // 128)), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    chunk = EP.decode_slots_paged.lower(
        cfg, params, state, pool, table, key, sparams, num_steps=16,
    ).compile()
    # the width the server launches (engine/scheduler.step_width): 128 /
    # 136 for the dense two, 512 for the routed one
    tile, width = 8, step_width(cfg, slots, 8)
    assert width == CELL_WIDTHS[config]
    entries = [(b, 0, 1, EP.RAGGED_DECODE) for b in range(slots)]
    meta, tok_row, tok_pos, offsets, _ = EP.build_ragged_meta(
        entries, width=width, tile=tile)
    dev = None if grouped else EP.DeviceMeta(*(
        S(a.shape, a.dtype) for a in EP.build_device_meta(
            entries, offsets, slots, width=width, tile=tile)))
    arm = place(jax.eval_shape(lambda: EP.idle_mixed_arm(slots, cfg.vocab_size)))
    flat = lambda a: S(np.shape(a), np.asarray(a).dtype)  # noqa: E731
    mixed = EP.mixed_step_ragged.lower(
        cfg, params, S((width,), jnp.int32), flat(tok_row), flat(tok_pos),
        S((width,), jnp.bool_), flat(meta), pool, table, state, sparams, key,
        S((slots,), jnp.int32), arm, dev=dev,
    ).compile()
    return params, pool, {"decode_slots_paged": chunk,
                          "mixed_step_ragged": mixed}


@pytest.mark.parametrize("config", sorted(CELL_PROGRAMS) + list(CELL_FILES))
def test_step_programs_hold_no_copy_of_the_pool_at_cell_sizes(
    one_chip, no_persistent_cache, monkeypatch, config
):
    import re

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    _, pool, programs = _cell_step_programs(one_chip, config)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert pool_bytes > 2e9
    for name, compiled in programs.items():
        memory = compiled.memory_analysis()
        # the pool goes in and comes out as one buffer ...
        assert memory.alias_size_in_bytes >= pool_bytes - 2**20, (name, memory)
        # ... and the temporaries hold nothing of its size
        assert memory.temp_size_in_bytes < 0.45 * pool_bytes, (name, memory)
        text = compiled.as_text()
        assert _pool_sized_instructions(text, pool) == [], name
        _assert_scopes(text, name, DENSE_SCOPES + (
            ROUTED_SCOPES + ("moe_shared", "mla_absorb")
            if config.startswith("kanana") else
            ROUTED_SCOPES + ("moe_shared",) if config in CELL_FILES else ()))
        if config in CELL_FILES:
            mem = compiled.memory_analysis()
            print(f"{config} {name}: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
                  f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB, aliased "
                  f"{mem.alias_size_in_bytes / 1e9:.3f} GB of a {pool_bytes / 1e9:.3f} GB pool")
            # the sliced head: 25,024 columns are 195.5 lane tiles of 128
            print("head product shapes:", sorted(set(re.findall(
                r"(?:f32|bf16)\[\d+,250(?:24|88)\]", text))))


@pytest.mark.parametrize("config", sorted(CELL_PROGRAMS) + list(CELL_FILES)
                         + ["sdar-30b-a3b-7l"])
def test_step_programs_read_the_attention_projections_in_place(
    one_chip, no_persistent_cache, monkeypatch, config
):
    """ISSUE 39: in both step programs of the four scanned configurations,
    at the cells' sizes and with every routed or dense stack but kanana's
    one leading layer longer than one, q / k / v (kanana: the query
    projection) and `wo` are read by their dots from the stacked parameter:
    no slice copy a layer-step, no relayout of a stack a launch
    (`models/llama.pin_products` says what made them)."""
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    if config in CELL_PROGRAMS or config in CELL_FILES:
        params, _, programs = _cell_step_programs(one_chip, config)
        texts = {name: c.as_text() for name, c in programs.items()}
    else:
        params, texts = _block_diffusion_step_programs(one_chip)
    assert set(texts) == {"decode_slots_paged", "mixed_step_ragged"}
    for name, text in texts.items():
        assert _projection_sized_instructions(text, params["layers"]) == [], name


# -- the latent-attention, routed-expert family (ISSUE 28) ---------------------
#
# kanana-2-30b-a3b at its published widths: the paged kernels' latent form
# (one 640-number row a token, 32 query heads on it), the routed experts'
# grouped matrix product over the STACKED bank, and the two step programs
# (cut to the dense layer and one expert layer: the names do not depend on
# depth) with the names `cellbench/configs/kanana-2-30b-a3b-7l.json` gives.
#
# `routed_expert_matmul` is JAX's megablox `gmm` called through its private
# `__wrapped__` (the undecorated function under `gmm`'s own jit), only so that
# the custom call carries this program's name: a JAX release that drops the
# attribute fails the two tests below first, not the benchmark's readers.
# The six configurations as their cells serve them: both kernels as the
# step programs call them (the stacked pool, written in place), at the
# (KV heads, pages a loop step) their shapes get (ops/paged_attention.
# _walk_shape; tests/test_launch_record.py pins the numbers), by the step
# program that calls them. A grouped pool (trinity) compiles its window
# group's leaf under the window.
@pytest.mark.parametrize("program", ["decode_slots_paged", "mixed_step_ragged"])
@pytest.mark.parametrize("config", CELL_CONFIGS)
def test_paged_kernels_compile_writing_in_place_at_every_cells_shapes(
    one_chip, no_persistent_cache, monkeypatch, config, program
):
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, slots, mb, pool = cell_pool(config)
    names = EP.GROUP_LEAVES[1] if len(cfg.kv_groups) > 1 else (
        ("k", "v") if "k" in pool else ("moe", None))
    S = _spec(one_chip)
    pool_k, pool_v = (n and S(pool[n].shape, pool[n].dtype) for n in names)
    kv, _, width = pool_k.shape[-3:]
    kw = dict(window=cfg.attn_window or None, scale=cfg.query_scale,
              value_dim=cfg.kv_lora_rank if pool_v is None else None)
    table = S((slots, mb), jnp.int32)
    kernel = "ragged_paged_attend"
    if program == "decode_slots_paged" and not cfg.diffusion_block:
        kernel = "paged_flash_attend"
        new = S((slots, 1, kv, width), jnp.bfloat16)
        text = _compile(
            lambda q, pk, pv, t, pos, live, layer, k, v: paged_flash_attend(
                q, pk, pv, t, pos, None, live,
                (layer, k, None if pv is None else v), **kw),
            S((slots, 1, cfg.n_heads, width), jnp.bfloat16), pool_k, pool_v,
            table, S((slots,), jnp.int32), S((slots,), jnp.bool_),
            S((), jnp.int32), new, new)
    else:  # a block-diffusion row's forward is a query tile in both programs
        flat = slots * 8 if program == "decode_slots_paged" else step_width(
            cfg, slots, 8)
        new = S((flat, kv, width), jnp.bfloat16)
        text = _compile(
            lambda q, pk, pv, t, m, layer, k, v: ragged_paged_attend(
                q, pk, pv, t, m, None, (layer, k, None if pv is None else v),
                block=cfg.diffusion_block, **kw),
            S((flat, cfg.n_heads, width), jnp.bfloat16), pool_k, pool_v,
            table, S((flat // 8, 4), jnp.int32), S((), jnp.int32), new, new)
    assert any(kernel in c for c in _custom_call_names(text))


EXPERT_KERNELS = {"routed_expert_matmul"}
STEP_SCOPES = {"moe_route", "moe_dispatch", "moe_experts", "moe_combine",
               "moe_shared", "mla_absorb"}
LATENT_SLOTS, LATENT_BLOCKS, LATENT_CONTEXT = 8, 1750, 32768


@pytest.mark.parametrize("tq", [1, 8])
def test_latent_walk_compiles_at_cell_shapes(one_chip, no_persistent_cache, tq):
    S = _spec(one_chip)
    rows, r = 640, 512
    width = step_width(get_model_config("kanana-2-30b-a3b"), LATENT_SLOTS, 8)
    assert width == 512
    pool = S((LATENT_BLOCKS, 1, 128, rows), jnp.bfloat16)
    table = S((LATENT_SLOTS, LATENT_CONTEXT // 128), jnp.int32)
    if tq == 1:
        text = _compile(
            functools.partial(paged_flash_attend, interpret=False,
                              scale=192 ** -0.5, value_dim=r),
            S((LATENT_SLOTS, 1, 32, rows), jnp.bfloat16), pool, None, table,
            S((LATENT_SLOTS,), jnp.int32))
    else:
        text = _compile(
            functools.partial(ragged_paged_attend, interpret=False,
                              scale=192 ** -0.5, value_dim=r),
            S((width, 32, rows), jnp.bfloat16), pool, None, table,
            S((width // tq, 4), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pairs", [48, 768, 3072])
def test_routed_expert_matmul_compiles_at_cell_shapes(
    one_chip, no_persistent_cache, monkeypatch, pairs
):
    """A decode chunk's 8 rows x 6 and a mixed step's 512 tokens x 6 (128
    until ISSUE 37), over the six expert layers' stacked bank (never a
    per-layer copy of it)."""
    from distributed_llm_inference_tpu.models import mla_moe as MM

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    S = _spec(one_chip)
    for k, n in ((2048, 768), (768, 2048)):
        text = _compile(
            MM.grouped_matmul, S((pairs, k), jnp.bfloat16),
            S((6, 128, k, n), jnp.bfloat16), S((128,), jnp.int32),
            S((), jnp.int32))
        assert any("routed_expert_matmul" in c for c in _custom_call_names(text))


def test_latent_step_programs_carry_the_names_a_trace_is_read_by(
    one_chip, no_persistent_cache, monkeypatch
):
    import json
    import os
    import re

    import numpy as np

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg = resolve_attn_impl(
        get_model_config("kanana-2-30b-a3b").replace(n_layers=2, dtype="bfloat16"),
        "pallas",
    )
    S = _spec(one_chip)
    place = functools.partial(_placed, sharding=one_chip)
    params = place(jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    slots = LATENT_SLOTS
    state, sparams = place(jax.eval_shape(lambda: G.init_slots(slots, cfg.vocab_size)))
    pool = place(jax.eval_shape(lambda: EP.init_pool(cfg, LATENT_BLOCKS, 128)))
    assert pool["moe"].shape == (1, LATENT_BLOCKS, 1, 128, 640)
    table = S((slots, LATENT_CONTEXT // 128), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    chunk = EP.decode_slots_paged.lower(
        cfg, params, state, pool, table, key, sparams, num_steps=2,
    ).compile().as_text()
    tile, width = 8, step_width(cfg, slots, 8)
    assert width == 512
    entries = [(b, 0, 1, EP.RAGGED_DECODE) for b in range(slots)]
    meta, tok_row, tok_pos, offsets, _ = EP.build_ragged_meta(
        entries, width=width, tile=tile)
    dev = EP.DeviceMeta(*(
        S(a.shape, a.dtype) for a in EP.build_device_meta(
            entries, offsets, slots, width=width, tile=tile)))
    arm = place(jax.eval_shape(lambda: EP.idle_mixed_arm(slots, cfg.vocab_size)))
    flat = lambda a: S(np.shape(a), np.asarray(a).dtype)  # noqa: E731
    mixed = EP.mixed_step_ragged.lower(
        cfg, params, S((width,), jnp.int32), flat(tok_row), flat(tok_pos),
        S((width,), jnp.bool_), flat(meta), pool, table, state, sparams, key,
        S((slots,), jnp.int32), arm, dev=dev,
    ).compile().as_text()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "kanana-2-30b-a3b-7l.json")) as f:
        trace = json.load(f)["serving"]["trace"]
    assert set(trace["expert_kernels"]) == EXPERT_KERNELS
    for module, text, kernel in (("decode_slots_paged", chunk, "paged_flash_attend"),
                                 ("mixed_step_ragged", mixed, "ragged_paged_attend")):
        assert module in _module_name(text)
        calls = _custom_call_names(text)
        for name in (kernel, *trace["expert_kernels"]):
            assert any(name in c for c in calls), (module, name, sorted(calls))
        stacks = " ".join(set(re.findall(r'op_name="([^"]*)"', text)))
        for scope in STEP_SCOPES:
            assert scope in stacks, (module, scope)
        _assert_scopes(text, module, DENSE_SCOPES + tuple(STEP_SCOPES))


@pytest.mark.parametrize("tq", [4, 8], ids=["open-block", "owed-and-open"])
def test_ragged_kernel_compiles_with_the_block_mask(
    one_chip, no_persistent_cache, monkeypatch, tq
):
    """sdar-batch's attention: 4 KV heads of 128, 128-token blocks, the
    block-diffusion mask static in the kernel; a tile of 8 is a row's owed
    and open blocks in both step programs (ISSUE 33), a tile of 4 the open
    block alone (PR 32's decode chunk)."""
    from distributed_llm_inference_tpu.ops.paged_attention import ragged_paged_attend

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    S = _spec(one_chip)
    slots, width = 32, 32 * tq
    pool = S((7, 512, 4, 128, 128), jnp.bfloat16)
    new = S((width, 4, 128), jnp.bfloat16)
    text = _compile(
        lambda q, pk, pv, t, m, layer, k, v: ragged_paged_attend(
            q, pk, pv, t, m, None, (layer, k, v), block=4),
        S((width, 32, 128), jnp.bfloat16), pool, pool,
        S((slots, 16), jnp.int32), S((slots, 4), jnp.int32), S((), jnp.int32),
        new, new)
    assert any("ragged_paged_attend" in c for c in _custom_call_names(text))


@functools.cache
def _block_diffusion_step_programs(one_chip):
    """(params, {module: optimized HLO text}) of sdar-30b-a3b-chat cut to 2
    layers at sdar-batch's sizes: a decode row is one tile of 8 (its owed
    and open blocks, 32 slots x 8 = 256 flat tokens in the decode chunk),
    the mixed launch 512 wide. Compiled once for the tests that read them;
    the caller has set DLI_PALLAS_INTERPRET=0."""
    import numpy as np

    cfg = resolve_attn_impl(
        get_model_config("sdar-30b-a3b-chat").replace(n_layers=2, dtype="bfloat16"),
        "pallas",
    )
    S = _spec(one_chip)
    place = functools.partial(_placed, sharding=one_chip)
    params = place(jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    slots, blocks, context, tile = 32, 512, 2048, 8
    state, sparams = place(jax.eval_shape(lambda: G.init_slots(slots, cfg.vocab_size)))
    pool = place(jax.eval_shape(lambda: EP.init_pool(cfg, blocks, 128)))
    assert pool["routed"].shape == (2, 2, 128)
    diff = place(jax.eval_shape(lambda: EP.init_diffusion(cfg, slots)))
    table = S((slots, context // 128), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    chunk = EP.decode_slots_paged.lower(
        cfg, params, state, pool, table, key, sparams, num_steps=2, diff=diff,
    ).compile().as_text()
    width = step_width(cfg, slots, tile)
    # half the rows carry their owed block in front of the open one
    owing = [b % 2 == 0 for b in range(slots)]
    entries = [(b, 0, 8 if owe else 4, EP.RAGGED_PREFILL)
               for b, owe in zip(range(slots), owing)]
    meta, tok_row, tok_pos, offsets, _ = EP.build_ragged_meta(
        entries, width=width, tile=tile)
    # one tile a row, as before, in a launch of 512 (264 until ISSUE 37)
    assert width == 512 and len(offsets) == slots
    *dev, open_at = EP.build_block_meta(
        entries, offsets, owing, block=4, width=width, tile=tile)
    assert open_at[:2] == [4, 8]
    dev = EP.DeviceMeta(*(S(a.shape, a.dtype) for a in dev))
    arm = place(jax.eval_shape(lambda: EP.idle_mixed_arm(slots, cfg.vocab_size)))
    darm = diff
    flat = lambda a: S(np.shape(a), np.asarray(a).dtype)  # noqa: E731
    mixed = EP.mixed_step_ragged.lower(
        cfg, params, S((width,), jnp.int32), flat(tok_row), flat(tok_pos),
        S((width,), jnp.bool_), flat(meta), pool, table, state, sparams, key,
        S((slots,), jnp.int32), arm, dev=dev, diff=diff, darm=darm,
    ).compile().as_text()
    return params, {"decode_slots_paged": chunk, "mixed_step_ragged": mixed}


def test_block_diffusion_step_programs_carry_the_names_a_trace_is_read_by(
    one_chip, no_persistent_cache, monkeypatch
):
    """sdar-30b-a3b-chat (cut to 2 layers) at sdar-batch's sizes: both step
    programs compile for the chip, keep their module names, read the pool
    through the ragged kernel (a decode row is one tile of 8: its owed and
    open blocks, 32 slots x 8 = 256 flat tokens in the decode chunk) and run
    the routed experts' grouped kernel under the scopes kanana's do."""
    import json
    import os
    import re

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    _, texts = _block_diffusion_step_programs(one_chip)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "sdar-30b-a3b-7l.json")) as f:
        trace = json.load(f)["serving"]["trace"]
    assert set(trace["expert_kernels"]) == EXPERT_KERNELS
    assert set(trace["step_modules"]) == {"decode_slots_paged", "mixed_step_ragged"}
    # the decode chunk's flat axis: 32 slots x 2 blocks of 4 = 256 tokens
    assert "bf16[256,2048]" in texts["decode_slots_paged"]
    for module, text in texts.items():
        assert module in _module_name(text)
        calls = _custom_call_names(text)
        for name in ("ragged_paged_attend", *trace["expert_kernels"]):
            assert any(name in c for c in calls), (module, name, sorted(calls))
        assert "ragged_paged_attend" in trace["attention_kernels"]
        stacks = " ".join(set(re.findall(r'op_name="([^"]*)"', text)))
        for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine"):
            assert scope in stacks, (module, scope)
        # (every layer routes: the family's programs hold no dense `ffn`)
        _assert_scopes(text, module,
                       ("embed", "attn", "head", "sample") + ROUTED_SCOPES)
        # the expert banks ride outside the layer scan: no operation of a
        # bank's size (2 x 128 experts of 2048 x 768) beside the kernel
        assert not re.search(r"copy\(.*bf16\[256,(2048,768|768,2048)\]", text), module


# -- gated short convolutions beside head-dim-64 attention (ISSUE 34) -----------
#
# lfm2-24b-a2b cut to the cell's 9 layers (cellbench/configs/lfm2-24b-a2b-9l.json)
# at lfm2-docs-long's sizes. The stack is unrolled (layers of two kinds), K/V
# belongs to 2 of the 9 layers and stores pairs of 64-number heads side by side
# on 128 lanes, so both paged kernels write in place; a slot's convolution
# state and a block's state tail ride the same donated pool.
def test_conv_hybrid_step_programs_at_cell_sizes_write_in_place(
    one_chip, no_persistent_cache, monkeypatch
):
    import json
    import os
    import re

    import numpy as np

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs", "lfm2-24b-a2b-9l.json")) as f:
        config = json.load(f)
    serving, flags = config["serving"], config["serving"]["flags"]
    flag = lambda name: int(flags[flags.index(name) + 1])  # noqa: E731
    slots, blocks, context = (flag("--continuous"), flag("--kv-pool-blocks"),
                              flag("--continuous-max-seq"))
    cfg = resolve_attn_impl(
        get_model_config(serving["base"]).replace(dtype="bfloat16", **serving["overrides"]),
        "pallas",
    )
    assert (len(cfg.conv_layers), len(cfg.attn_layers), cfg.kv_pack) == (7, 2, 2)
    S = _spec(one_chip)
    place = functools.partial(_placed, sharding=one_chip)
    params = place(jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    state, sparams = place(jax.eval_shape(lambda: G.init_slots(slots, cfg.vocab_size)))
    pool = place(jax.eval_shape(lambda: EP.init_pool(cfg, blocks, 128, n_slots=slots)))
    assert pool["k"].shape == (2, blocks, 4, 128, 128)  # whole 128-lane tiles
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    tail_bytes = pool["tail"].size * 2
    table = S((slots, context // 128), jnp.int32)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    chunk = EP.decode_slots_paged.lower(
        cfg, params, state, pool, table, key, sparams, num_steps=16,
    ).compile()
    tile, width = 8, step_width(cfg, slots, 8)
    assert width == 512
    # (a fleet with recurrent state drafts nothing: no DeviceMeta operand)
    entries = [(b, 0, 1, EP.RAGGED_DECODE) for b in range(slots - 1)]
    entries.append((slots - 1, 0, 8, EP.RAGGED_FIRST))
    meta, tok_row, tok_pos, _, _ = EP.build_ragged_meta(entries, width=width, tile=tile)
    arm = place(jax.eval_shape(lambda: EP.idle_mixed_arm(slots, cfg.vocab_size)))
    flat = lambda a: S(np.shape(a), np.asarray(a).dtype)  # noqa: E731
    mixed = EP.mixed_step_ragged.lower(
        cfg, params, S((width,), jnp.int32), flat(tok_row), flat(tok_pos),
        S((width,), jnp.bool_), flat(meta), pool, table, state, sparams, key,
        S((slots,), jnp.int32), arm,
    ).compile()
    trace = serving["trace"]
    assert set(trace["step_modules"]) == {"decode_slots_paged", "mixed_step_ragged"}
    assert set(trace["expert_kernels"]) == EXPERT_KERNELS
    for module, kernel, compiled in (("decode_slots_paged", "paged_flash_attend", chunk),
                                     ("mixed_step_ragged", "ragged_paged_attend", mixed)):
        memory = compiled.memory_analysis()
        print(f"{module}: temporaries {memory.temp_size_in_bytes / 1e6:.1f} MB, "
              f"aliased {memory.alias_size_in_bytes / 1e9:.3f} GB of a "
              f"{pool_bytes / 1e9:.3f} GB pool")
        # the pool (K/V, the slots' state, the blocks' tails) goes in and
        # comes out as one buffer ...
        assert memory.alias_size_in_bytes >= pool_bytes - 2**20, (module, memory)
        # ... and all the temporaries together are smaller than the
        # smallest thing a copy could be of: the tails (0.2 GB), a layer's
        # slice of K or V (0.46 GB), an expert bank (1.6 GB); the 512-wide
        # mixed step's are 0.11 GB, twice the 136-wide one's
        assert memory.temp_size_in_bytes < 0.6 * tail_bytes, (module, memory)
        text = compiled.as_text()
        assert _pool_sized_instructions(text, pool) == [], module
        assert not re.search(
            r"copy\(.*bf16\[(8,64|512),(2048,1536|1536,2048)\]", text), module
        assert module in _module_name(text)
        calls = _custom_call_names(text)
        for name in (kernel, *trace["expert_kernels"]):
            assert any(name in c for c in calls), (module, name, sorted(calls))
        assert kernel in trace["attention_kernels"]
        stacks = " ".join(set(re.findall(r'op_name="([^"]*)"', text)))
        for scope in ("conv_mix", "moe_route", "moe_dispatch", "moe_experts", "moe_combine"):
            assert scope in stacks, (module, scope)
        _assert_scopes(text, module, DENSE_SCOPES + ROUTED_SCOPES + ("conv_mix",))


def test_two_compiles_compare_equal_once_source_positions_are_out(
    one_chip, no_persistent_cache, monkeypatch
):
    """tests/dense_equal.py holds one checkout's compiled dense step programs
    against another's (ISSUE 28): `canon` leaves the instructions and the
    Mosaic kernels and takes out what only says where a line of source
    stands, so a moved line is no difference and a changed instruction is."""
    import re

    import dense_equal

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    texts = dense_equal.programs("test-llama-tiny", 4, 16, 128, block_size=16)
    assert set(texts) == {"decode_slots_paged", "mixed_step_ragged"}
    for name, text in texts.items():
        body, kernels = dense_equal.canon(text)
        assert name in body.split("\n", 1)[0] and len(kernels) == 1
        assert "op_name=" not in body and "paged.py" not in body and "loc(" not in kernels[0]
        moved = re.sub(r"line=(\d+)", lambda m: f"line={int(m.group(1)) + 7}", text)
        assert moved != text and dense_equal.canon(moved) == (body, kernels)
        changed = text.replace(" multiply(", " add(", 1)
        assert changed != text and dense_equal.canon(changed)[0] != body
        # two trees whose metadata alone differs may number an instruction
        # differently (ISSUE 38): the same instructions in the same order
        # compare equal once renumbered by place, a changed one does not
        shifted = re.sub(r"(%[a-z_\-]+)\.(\d+)",
                         lambda m: f"{m.group(1)}.{int(m.group(2)) + 1}", body)
        assert shifted != body
        assert dense_equal.renumbered(shifted) == dense_equal.renumbered(body)
        assert dense_equal.renumbered(dense_equal.canon(changed)[0]) \
            != dense_equal.renumbered(body)
