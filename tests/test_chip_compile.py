"""Compile the main path's KERNELS, each alone, for a TPU v5e that is
described, not attached: tests/described_chip.py has the why and the rules.
The whole step programs are compiled in tests/test_chip_tinyllama.py
(chip_smoke.py's fleet) and tests/test_cell_programs_*.py (the benchmark's
configurations), so that the suite's workers share the minutes out.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_inference_tpu.engine import paged as EP
from distributed_llm_inference_tpu.engine.scheduler import live_width, step_width
from distributed_llm_inference_tpu.ops import quant as Q
from distributed_llm_inference_tpu.ops.flash_attention import flash_attend
from distributed_llm_inference_tpu.ops.kv_quant import KVQuant
from distributed_llm_inference_tpu.ops.paged_attention import (
    paged_flash_attend,
    ragged_paged_attend,
)

from dense_equal import CELL_CONFIGS, CELL_FILES
from described_chip import (  # noqa: F401 - fixtures
    compile_text as _compile, custom_call_names as _custom_call_names,
    no_persistent_cache, one_chip, placed as _placed, spec as _spec, topo,
)
from paged_walk_cases import cell_pool

# TinyLlama-1.1B widths: 32 query heads over 4 kv heads, head_dim 64
H, KV, DH = 32, 4, 64
POOL_BLOCKS = 3072  # chip_smoke.py's pool: >= 1 GiB of bf16 KV at bs 16
SLOTS = 8


def test_every_configuration_of_the_benchmark_has_one_file_of_programs():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        named = {cell["config"] for cell in json.load(f)["workloads"]}
    assert named == set(CELL_CONFIGS)
    assert len(CELL_CONFIGS) == sum(map(len, CELL_FILES.values()))
    for name, configs in CELL_FILES.items():
        assert len(configs) <= 2, name
        assert os.path.exists(os.path.join(root, "tests", name + ".py")), name


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


# The two dense configurations as their cells serve them
# (cellbench/configs/*.json through paged_walk_cases.cell_pool): slots, query
# heads, KV heads, head dim, block size, table width, pool blocks, window.
DENSE_CELLS = ("mistral-7b-16l", "olmo2-7b-16l")


def _cell_shape(cell):
    cfg, slots, mb, pool = cell_pool(cell)
    _, blocks, kv, bs, dh = pool["k"].shape
    return cfg, slots, cfg.n_heads, kv, dh, bs, mb, blocks, cfg.attn_window or None


def test_the_dense_cells_shapes_are_their_files():
    assert _cell_shape("olmo2-7b-16l")[1:] == (12, 32, 32, 128, 128, 16, 61, None)
    assert _cell_shape("mistral-7b-16l")[1:] == (16, 32, 8, 128, 128, 50, 271, 4096)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("cell", DENSE_CELLS)
def test_paged_flash_attend_compiles_at_cell_shapes(
    one_chip, no_persistent_cache, cell, quant
):
    """The pure-decode chunk's kernel, with the slots' active mask."""
    _, slots, h, kv, dh, bs, mb, blocks, window = _cell_shape(cell)
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
@pytest.mark.parametrize("cell", DENSE_CELLS)
def test_ragged_paged_attend_compiles_at_cell_shapes(
    one_chip, no_persistent_cache, cell, quant
):
    """The mixed step's kernel: `step_width` flat tokens (the fleet's decode
    tiles and a dense model's 128 on top) in query tiles of 8 (28 tiles for
    olmo2's 12 slots, 32 for mistral's 16)."""
    cfg, slots, h, kv, dh, bs, mb, blocks, window = _cell_shape(cell)
    S = _spec(one_chip)
    pool = _kv(S, (blocks, kv, bs, dh), quant)
    tq = 8
    tiles = step_width(cfg, slots, tq) // tq
    assert tiles == {"olmo2-7b-16l": 28, "mistral-7b-16l": 32}[cell]
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


# The configurations as their cells serve them: both kernels as the
# step programs call them (the stacked pool, written in place), at the
# (KV heads, pages a loop step) their shapes get (ops/paged_attention.
# _walk_shape; tests/test_launch_record.py pins the numbers), by the step
# program that calls them. A grouped pool (trinity, mimo) compiles its window
# group's leaves under the window, with a sink a query head where the window
# layers have one, and its global group's too where that group's rows are
# another shape (mimo: 4 K/V heads beside 8; keys 256 lanes wide and values
# 128 in both).
@pytest.mark.parametrize("program", ["decode_slots_paged", "mixed_step_ragged"])
@pytest.mark.parametrize("config", CELL_CONFIGS)
def test_paged_kernels_compile_writing_in_place_at_every_cells_shapes(
    one_chip, no_persistent_cache, monkeypatch, config, program
):
    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, slots, mb, pool = cell_pool(config)
    S = _spec(one_chip)
    grouped = len(cfg.kv_groups) > 1
    # (leaves, the layers' window, whether they have a sink)
    cases = [(EP.GROUP_LEAVES[1] if grouped else
              ("k", "v") if "k" in pool else ("moe", None),
              cfg.attn_window or None, cfg.window_sink)]
    if grouped and pool["k"].shape[2:] != pool["kw"].shape[2:]:
        cases.append((EP.GROUP_LEAVES[0], None, False))
    for names, window, has_sink in cases:
        pool_k, pool_v = (n and S(pool[n].shape, pool[n].dtype) for n in names)
        kv, _, width = pool_k.shape[-3:]
        v_width = width if pool_v is None else pool_v.shape[-1]
        kw = dict(window=window, scale=cfg.query_scale,
                  value_dim=cfg.kv_lora_rank if pool_v is None else None)
        sink = S((cfg.n_heads,), jnp.float32) if has_sink else None
        table = S((slots, mb), jnp.int32)
        kernel = "ragged_paged_attend"
        if program == "decode_slots_paged" and not cfg.diffusion_block:
            kernel = "paged_flash_attend"
            text = _compile(
                lambda q, pk, pv, t, pos, live, layer, k, v, s: paged_flash_attend(
                    q, pk, pv, t, pos, None, live,
                    (layer, k, None if pv is None else v), None, s, **kw),
                S((slots, 1, cfg.n_heads, width), jnp.bfloat16), pool_k, pool_v,
                table, S((slots,), jnp.int32), S((slots,), jnp.bool_),
                S((), jnp.int32), S((slots, 1, kv, width), jnp.bfloat16),
                S((slots, 1, kv, v_width), jnp.bfloat16), sink)
        else:  # a block-diffusion row's forward is a query tile in both programs
            flat = slots * 8 if program == "decode_slots_paged" else step_width(
                cfg, slots, 8)
            text = _compile(
                lambda q, pk, pv, t, m, layer, k, v, s: ragged_paged_attend(
                    q, pk, pv, t, m, None, (layer, k, None if pv is None else v),
                    None, s, block=cfg.diffusion_block, **kw),
                S((flat, cfg.n_heads, width), jnp.bfloat16), pool_k, pool_v,
                table, S((flat // 8, 4), jnp.int32), S((), jnp.int32),
                S((flat, kv, width), jnp.bfloat16),
                S((flat, kv, v_width), jnp.bfloat16), sink)
        assert any(kernel in c for c in _custom_call_names(text))


def test_the_mimo_cells_four_kv_leaves_are_their_kinds_bytes():
    """mimo-v2.5-7l's pool as served: keys of 192 numbers on 256 lanes and
    values of 128, 4 K/V heads in the global group's 2 layers and 8 in the
    window group's 5: a block of the global group is 0.79 MB (0.66 MB of it
    useful), one of the window group 3.93 MB, 1.81 + 0.76 GB in all."""
    cfg, slots, mb, pool = cell_pool("mimo-v2.5-7l")
    assert (slots, mb, cfg.key_row, cfg.value_dim) == (32, 144, 256, 128)
    shapes = {n: pool[n].shape for n in ("k", "v", "kw", "vw")}
    assert shapes == {"k": (2, 2304, 4, 128, 256), "v": (2, 2304, 4, 128, 128),
                      "kw": (5, 193, 8, 128, 256), "vw": (5, 193, 8, 128, 128)}
    nbytes = {n: pool[n].size * 2 for n in shapes}
    assert nbytes["k"] + nbytes["v"] == 2304 * 2 * 4 * 128 * 384 * 2 == 1_811_939_328
    assert nbytes["kw"] + nbytes["vw"] == 193 * 5 * 8 * 128 * 384 * 2 == 758_906_880
    useful = 2 * 4 * 128 * (192 + 128) * 2
    assert (useful, (nbytes["k"] + nbytes["v"]) // 2304) == (655_360, 786_432)


# -- the latent-attention, routed-expert family (ISSUE 28) ---------------------
#
# kanana-2-30b-a3b at its published widths and its cell's sizes: the paged
# kernels' latent form (one 640-number row a token, 32 query heads on it)
# and the routed experts' grouped matrix product over the STACKED bank.
#
# `routed_expert_matmul` is JAX's megablox `gmm` called through its private
# `__wrapped__` (the undecorated function under `gmm`'s own jit), only so that
# the custom call carries this program's name: a JAX release that drops the
# attribute fails the test below first, not the benchmark's readers.
@pytest.mark.parametrize("tq", [1, 8])
def test_latent_walk_compiles_at_cell_shapes(one_chip, no_persistent_cache, tq):
    S = _spec(one_chip)
    cfg, slots, mb, cell = cell_pool("kanana-2-30b-a3b-7l")
    _, blocks, _, bs, rows = cell["moe"].shape
    r, width = cfg.kv_lora_rank, step_width(cfg, slots, 8)
    assert (slots, blocks, mb * bs, rows, r, width) == (8, 1750, 32768, 640, 512, 512)
    pool = S((blocks, 1, bs, rows), jnp.bfloat16)
    table = S((slots, mb), jnp.int32)
    if tq == 1:
        text = _compile(
            functools.partial(paged_flash_attend, interpret=False,
                              scale=192 ** -0.5, value_dim=r),
            S((slots, 1, 32, rows), jnp.bfloat16), pool, None, table,
            S((slots,), jnp.int32))
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


# -- the selected read (ISSUE 48) -----------------------------------------------
#
# minicpm-sala-9b-16l's sparse layers: both kernels walking a page LIST a KV
# head (2 of them, 16 query heads each) over 64-token pages, writing in
# place: a decode row's list is at most 128 pages (the dense length's), a
# mixed tile of 8 queries walks their union (at most 512) under the
# per-query choices; the selection is XLA's and compiles with the step
# programs (tests/test_cell_programs_sala.py); the scan is the test below.
@pytest.mark.parametrize("program", ["decode_slots_paged", "mixed_step_ragged"])
def test_the_selected_read_compiles_at_the_sala_cells_shapes(
    one_chip, no_persistent_cache, monkeypatch, program
):
    from distributed_llm_inference_tpu.models.minicpm_sala import list_width

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    cfg, slots, mb, pool = cell_pool("minicpm-sala-9b-16l")
    S = _spec(one_chip)
    pool_k = S(pool["k"].shape, pool["k"].dtype)
    layers, blocks, kv, bs, dh = pool_k.shape
    assert (layers, blocks, kv, bs, dh, slots, mb) == (4, 9216, 2, 64, 128, 16, 1032)
    table = S((slots, mb), jnp.int32)
    if program == "decode_slots_paged":
        L = list_width(cfg, 1, mb)
        assert L == 128
        new = S((slots, 1, kv, dh), jnp.bfloat16)
        text = _compile(
            lambda q, pk, pv, t, pos, live, layer, k, v, plist, count:
            paged_flash_attend(q, pk, pv, t, pos, None, live, (layer, k, v),
                               (plist, count)),
            S((slots, 1, cfg.n_heads, dh), jnp.bfloat16), pool_k, pool_k,
            table, S((slots,), jnp.int32), S((slots,), jnp.bool_),
            S((), jnp.int32), new, new, S((slots, kv, L), jnp.int32),
            S((slots, kv), jnp.int32))
        kernel = "paged_flash_attend"
    else:
        flat, tq = step_width(cfg, slots, 8), 8
        L = list_width(cfg, tq, mb)
        assert (flat, L) == (136, 512)
        new = S((flat, kv, dh), jnp.bfloat16)
        text = _compile(
            lambda q, pk, pv, t, m, layer, k, v, plist, count, chosen:
            ragged_paged_attend(q, pk, pv, t, m, None, (layer, k, v),
                                (plist, count, chosen)),
            S((flat, cfg.n_heads, dh), jnp.bfloat16), pool_k, pool_k, table,
            S((flat // tq, 4), jnp.int32), S((), jnp.int32), new, new,
            S((flat // tq, kv, L), jnp.int32), S((flat // tq, kv), jnp.int32),
            S((flat, kv, L), jnp.bool_))
        kernel = "ragged_paged_attend"
    assert any(kernel in c for c in _custom_call_names(text))


# minicpm-sala-9b-16l's linear layers: the scan's program at the cell's
# shapes (16 slots, 32 heads of 128, a float32 state a row and head): a mixed
# launch's 17 tiles of 8 and the decode chunk's one token a row. The state
# leaf goes in and comes out as one buffer, and the call is named by its
# scope: the label `linear_attn_roofline` finds it by.
@pytest.mark.parametrize("flat,tq", [(136, 8), (16, 1)], ids=["mixed", "decode"])
def test_the_linear_scan_compiles_at_the_sala_cells_shapes(
    one_chip, no_persistent_cache, flat, tq
):
    from distributed_llm_inference_tpu.ops.linear_attention import (
        linear_attend_rows,
    )

    cfg, slots, _, pool = cell_pool("minicpm-sala-9b-16l")
    S = _spec(one_chip)
    lin = pool["lin"][0]
    assert (slots, step_width(cfg, slots, 8)) == (16, 136)
    assert (lin.shape, lin.dtype) == ((16, 32, 128, 128), jnp.float32)
    tokens = S((flat, cfg.linear_heads, cfg.head_dim), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, state, tok_row: linear_attend_rows(
            q, k, v, state, tok_row, tq, interpret=False),
        donate_argnums=(3,),
    ).lower(tokens, tokens, tokens, S(lin.shape, lin.dtype),
            S((flat,), jnp.int32)).compile()
    text = compiled.as_text()
    assert any("linear_scan" in c for c in _custom_call_names(text))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == lin.size * 4, memory
    # nothing of a leaf's size beside the leaf: the tokens and their masks
    assert memory.temp_size_in_bytes < lin.size * 4 // 8, memory
    # ... and no instruction but the call makes a buffer of a leaf's shape
    made = re.findall(r"= f32\[16,32,128,128\]\{[^}]*\} ([\w\-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast"}, made


# granite-4.0-h-micro's mamba layers: the state-space scan's program at the
# cell's shapes (64 slots, 64 heads of 64 over a state of 128, packed two a
# 128-lane row: a float32 state [32, 128, 128] a row): a mixed launch's live
# tokens side by side (`scheduler.live_width`'s 320 at 64 slots: no tiles, so a
# chunk's blocks start anywhere), the tile layout it replaced there (64 tiles
# of 8 and 128 prompt tokens on top, `scheduler.step_width`'s 640: what an
# explicit budget still runs) and the decode chunk's one token a row. The state
# leaf goes in and comes out as one buffer, and the call is named `ssm_scan`
# under its scope: what `ssm_scan_roofline` finds it by.
@pytest.mark.parametrize("flat,tq", [(320, 1), (640, 8), (64, 1)],
                         ids=["mixed", "tiles", "decode"])
def test_the_ssm_scan_compiles_at_the_granite_cells_shapes(
    one_chip, no_persistent_cache, flat, tq
):
    from distributed_llm_inference_tpu.ops.ssm_scan import ssm_scan_rows

    cfg, slots, _, pool = cell_pool("granite-4.0-h-micro")
    S = _spec(one_chip)
    lin = pool["lin"][0]
    assert (slots, step_width(cfg, slots, 8), live_width(cfg, slots, 8)) == (
        64, 640, 320)
    assert (lin.shape, lin.dtype) == ((64, 32, 128, 128), jnp.float32)
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    compiled = jax.jit(
        lambda x, dt, A, B, C, state, tok_row, zero: ssm_scan_rows(
            x, dt, A, B, C, state, tok_row, tq, zero=zero, interpret=False),
        donate_argnums=(5,),
    ).lower(S((flat, H, P), jnp.float32), S((flat, H), jnp.float32),
            S((H,), jnp.float32), S((flat, N), jnp.float32),
            S((flat, N), jnp.float32), S(lin.shape, lin.dtype),
            S((flat,), jnp.int32), S((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert any("ssm_scan" in c for c in _custom_call_names(text))
    assert "ssm_scan/jit(ssm_scan)" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == lin.size * 4, memory
    # nothing of the leaf's size beside the leaf: the tokens, and in a mixed
    # launch the [64, W, W] decays of the within-launch part (W 640 or 320)
    assert memory.temp_size_in_bytes < lin.size * 4 // 2, memory
    made = re.findall(r"= f32\[64,32,128,128\]\{[^}]*\} ([\w\-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast",
                         "custom-call"}, made


# minicpm-sala-9b-16l's sparse layers: the selection's program at the cell's
# shapes (a table of 1,032 blocks, a leaf of 9,216 whole (16, 128) tiles of
# bfloat16, 2 KV heads of 16 query heads): a mixed launch's 17 tiles of 8 and
# the decode chunk's tile a slot. What interpret mode cannot show: the copies
# of a leaf's block (a whole tile), the stores of the laid-out keys, the
# search's integer keys, and the working set (the row's keys, 2.4 MB, two
# buffers of 16 blocks and the tile's scores and choices).
@pytest.mark.parametrize("flat,tq", [(136, 8), (16, 1)], ids=["mixed", "decode"])
def test_the_selection_compiles_at_the_sala_cells_shapes(
    one_chip, no_persistent_cache, flat, tq
):
    from distributed_llm_inference_tpu.ops.sparse_select import select_blocks

    cfg, slots, mb, pool = cell_pool("minicpm-sala-9b-16l")
    S = _spec(one_chip)
    leaf = pool["ck"][0]
    assert (slots, mb) == (16, 1032)
    assert (leaf.shape, leaf.dtype) == ((9216, 16, 128), jnp.bfloat16)
    kv, group = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    compiled = jax.jit(
        lambda q, leaf, table, tiles: select_blocks(
            q, leaf, table, tiles, block=cfg.sparse_block,
            stride=cfg.sparse_stride, kernel=cfg.sparse_kernel,
            topk=cfg.sparse_topk, window=cfg.sparse_window,
            init=cfg.sparse_init_blocks, dense_len=cfg.sparse_dense_len,
            interpret=False),
    ).lower(S((flat // tq, tq, kv, group, cfg.head_dim), jnp.bfloat16),
            S(leaf.shape, leaf.dtype), S((slots, mb), jnp.int32),
            S((flat // tq, 4), jnp.int32)).compile()
    text = compiled.as_text()
    assert any("select_blocks" in c for c in _custom_call_names(text))
    # the leaf is read where it lies: no buffer of its shape, or of a
    # gathered table's, beside the parameter
    made = re.findall(r"= bf16\[(?:9216,16,128|\d+,4128,[\d,]+)\]\{[^}]*\} "
                      r"([\w\-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast"}, made
    assert compiled.memory_analysis().temp_size_in_bytes < 2**22


# solar-open2-ep8-4l's KDA layers: the delta rule at the cell's shapes (64
# heads of 128 x 128, a [16, 64, 128, 128] float32 leaf a layer, 4.19 MB a
# row): the mixed launch's 512 flat tokens in the tile layout (8 chunks of the
# rule's 64), the same chunked form over one token a row, and the decode
# program's one-token form ("step": `delta_rule_step`, ISSUE 58). The state
# leaf goes in and comes out as one buffer, and the call is named
# `delta_state` (the one-token form's: `delta_step`) under its scope: what
# `delta_scan_roofline` finds it by. float32 is the
# configuration's STATED state: the leaf's dtype here and in
# tests/cell_program_checks.py, and tests/test_solar_ops.py's 2e-4 bound,
# hold it whatever `correct` can tell.
@pytest.mark.parametrize("flat,tq,kernel", [
    (512, 8, "delta_state"), (16, 1, "delta_state"), (16, 1, "delta_step")],
    ids=["mixed", "decode", "step"])
def test_the_delta_rule_compiles_at_the_solar_cells_shapes(
    one_chip, no_persistent_cache, flat, tq, kernel
):
    from distributed_llm_inference_tpu.ops.delta_rule import (
        CHUNK, delta_rule_rows, delta_rule_step)

    cfg, slots, _, pool = cell_pool("solar-open2-ep8-4l")
    S = _spec(one_chip)
    lin = pool["lin"][0]
    assert (slots, step_width(cfg, slots, 8), live_width(cfg, slots, 8)) == (
        16, 512, 512)
    assert (lin.shape, lin.dtype) == ((16, 64, 128, 128), jnp.float32)
    assert len(pool["lin"]) == len(cfg.delta_layers) == 3 and 512 % CHUNK == 0
    H, Dh = cfg.linear_heads, cfg.head_dim

    def rule(q, k, v, g, beta, state, tok_row, zero):
        if kernel == "delta_step":  # (the decode program starts no tenant)
            return delta_rule_step(q, k, v, g, beta, state, tok_row,
                                   interpret=False)
        return delta_rule_rows(q, k, v, g, beta, state, tok_row, tq,
                               zero=zero, interpret=False)

    compiled = jax.jit(rule, donate_argnums=(5,)).lower(
        *(S((flat, H, Dh), jnp.float32),) * 4, S((flat, H), jnp.float32),
        S(lin.shape, lin.dtype), S((flat,), jnp.int32),
        S((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert {c.split(".")[0] for c in _custom_call_names(text)
            if "delta_st" in c} == {kernel}
    assert f"delta_scan/jit({kernel})" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == lin.size * 4, memory
    # the chunk algebra's operands over the launch's tokens (and, while the
    # pair products' decays are written out once a launch, 268 MB of them:
    # PERF.md section 7), nothing a decode step could notice
    assert memory.temp_size_in_bytes < (450e6 if flat == 512 else 8e6), memory
    made = re.findall(r"= f32\[16,64,128,128\]\{[^}]*\} ([\w\-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast",
                         "custom-call"}, made
    if kernel == "delta_step":
        # the recurrence itself: 16 heads a program (4 x 16 grid steps a
        # layer), no chunk of 64, no pair product, no product on the matrix
        # unit, and a quarter of the scoped VMEM limit for the state's blocks
        assert not re.search(r"f32\[[\d,]*16,16,128\]", text)
        assert not re.search(r" (?:dot|convolution)\(", text)
        assert memory.temp_size_in_bytes < 1e6, memory
        call = next(line for line in text.splitlines()
                    if re.search(r"%delta_step[\w.\-]* = .*custom-call\(", line))
        assert "output_to_operand_aliasing={{1}: (7, {})}" in call


@pytest.mark.parametrize("pairs", [128, 4096])
def test_routed_expert_matmul_compiles_at_the_solar_cells_widths(
    one_chip, no_persistent_cache, monkeypatch, pairs
):
    """A decode chunk's 16 rows x 8 and a mixed step's 512 tokens x 8, over
    the four layers' stacked bank of 40 held experts of 4,096 x 1,280: an
    inner width that halves once (1,280 -> 640) and no further, where
    `_group_tiling` then cuts the columns so that two buffers of a tile stay
    inside the scoped VMEM (16.31 MB of 16 before it did: this PR's first
    described-chip compile)."""
    from distributed_llm_inference_tpu.models import experts as X

    monkeypatch.setenv("DLI_PALLAS_INTERPRET", "0")
    S = _spec(one_chip)
    assert X._group_tiling(4096, 1280, 4096, 2) == (128, 640, 2048)
    assert X._group_tiling(4096, 4096, 1280, 2) == (128, 1024, 1280)
    # (the accepted widths keep the tiles they had)
    assert X._group_tiling(3072, 2048, 768, 2) == (128, 2048, 768)
    assert X._group_tiling(2048, 3072, 3072, 2) == (128, 384, 3072)
    assert X._group_tiling(4096, 2048, 4096, 2) == (128, 256, 4096)
    for k, n in ((4096, 1280), (1280, 4096)):
        text = _compile(
            X.grouped_matmul, S((pairs, k), jnp.bfloat16),
            S((4, 40, k, n), jnp.bfloat16), S((40,), jnp.int32),
            S((), jnp.int32))
        assert any("routed_expert_matmul" in c for c in _custom_call_names(text))
