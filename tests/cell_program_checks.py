"""The rules a benchmark configuration's two step programs keep, compiled
at the cell's sizes and depth for a described v5e (tests/described_chip.py
has the rules of such compiles): every rule is one test over every
configuration of `dense_equal.CELL_FILES`, and a configuration a rule does
not apply to is named beside the rule with the reason, which the test
asserts in the rule's place.

A test file of the benchmark's configurations is three lines: it imports
this module's names (`from cell_program_checks import *`: the tests, the
fixtures and `pytest_generate_tests`, which gives the file the
configurations `CELL_FILES` lists under its name). A configuration is
compiled once in the whole suite: by `dense_equal.cell_programs`, memoised
in the worker that runs its file. A `model_config` PR adds its
configuration's name to `CELL_FILES` (and, past two a file, one such file).
"""

import re

import jax
import pytest  # noqa: F401 - the fixtures below are pytest's

from dense_equal import CELL_FILES, cell_programs, cell_serving
from described_chip import (  # noqa: F401 - fixtures, collected by the importer
    ATTENTION_KERNELS, DENSE_SCOPES, EXPERT_KERNELS, ROUTED_SCOPES,
    STEP_MODULES, assert_scopes, custom_call_names, module_name,
    no_persistent_cache, one_chip, topo,
)


def pytest_generate_tests(metafunc):
    if "config" in metafunc.fixturenames:
        metafunc.parametrize(
            "config", CELL_FILES[metafunc.module.__name__.rsplit(".", 1)[-1]])


# -- what a cell is ------------------------------------------------------------
#
# Asserted on the builder's result, which read the sizes from
# cellbench/configs/<name>.json: the mixed launch's width in the kernel's
# tile layout (engine/scheduler.step_width: 128 for a dense model, 512 where
# the experts route, with the fleet's decode tiles on top where they would
# take a third of that) and `live`, the axis the token-wise layers run on
# where that is narrower (engine/scheduler.live_width), the pool's blocks (a
# grouped pool: the window group a
# quarter of the global one's, its row budget 37), one pool leaf's shape, the
# labels of the family (utils/tracing.STEP_SCOPES) and the least the pool
# holds (2 GB and more; sdar-batch's 32 rows of 2,048 tokens: 0.94 GB).
CELLS = {
    "kanana-2-30b-a3b-7l": dict(
        width=512, blocks=1750, leaf=("moe", (6, 1750, 1, 128, 640)),
        scopes=DENSE_SCOPES + ROUTED_SCOPES + ("moe_shared", "mla_absorb")),
    # (K/V belongs to 2 of the 9 layers and stores pairs of 64-number heads
    # side by side: whole 128-lane tiles)
    "lfm2-24b-a2b-9l": dict(
        width=512, blocks=3500, leaf=("k", (2, 3500, 4, 128, 128)),
        scopes=DENSE_SCOPES + ROUTED_SCOPES + ("conv_mix",)),
    # (K/V and the compressed keys belong to 4 of the 16 layers; a pool block
    # is one 64-token block of the selection; the linear layers' states and
    # snapshots are a leaf a layer)
    "minicpm-sala-9b-16l": dict(
        width=136, blocks=9216, leaf=("k", (4, 9216, 2, 64, 128)),
        scopes=DENSE_SCOPES + ("linear_attn", "linear_scan", "sparse_select")),
    # (K/V belongs to 4 of the 40 layers, heads packed two a row; the 36
    # mamba layers' convolution and matrix states and their snapshots are a
    # leaf a layer; 64 slots' tiles of 8 and the dense budget on top for prefill,
    # since the fleet's states outweigh the weights: 640)
    # (... of which at most 64 + 2 x 128 = 320 are live, the axis the
    # token-wise layers run on: engine/scheduler.live_width)
    "granite-4.0-h-micro": dict(
        width=640, live=320, blocks=2048, leaf=("k", (4, 2048, 4, 64, 128)),
        scopes=DENSE_SCOPES + ("ssm_mix", "ssm_scan")),
    # (16 slots' tiles and the dense 128 on top; the model computes the 136
    # it launched before: the budget clamped to the fleet and a prefill tile)
    "mistral-7b-16l": dict(
        width=256, live=136, blocks=271, leaf=("k", (16, 271, 8, 128, 128)),
        scopes=DENSE_SCOPES),
    "olmo2-7b-16l": dict(
        width=224, live=128, blocks=61, leaf=("k", (16, 61, 32, 128, 128)),
        scopes=DENSE_SCOPES),
    # (every layer routes: the family's programs hold no dense `ffn`)
    "sdar-30b-a3b-7l": dict(
        width=512, blocks=512, leaf=("routed", (2, 7, 128)), pool_bytes=0.9e9,
        scopes=("embed", "attn", "head", "sample") + ROUTED_SCOPES),
    "trinity-large-ep8-5l": dict(
        width=512, blocks=(4608, 1152), leaf=("kw", (4, 1152, 8, 128, 128)),
        scopes=DENSE_SCOPES + ROUTED_SCOPES + ("moe_shared",)),
    # (a group's rows are its own kind's: 4 K/V heads in the global group's
    # two layers, 8 in the window group's five, keys of 192 numbers on 256
    # lanes and values of 128; the window is one block, so the window group
    # is the 32 slots' budgets of 6 and its null block: a row carries at most
    # the 512 of the compact axis in a launch, whatever the 768 tile places)
    "mimo-v2.5-7l": dict(
        width=768, live=512, blocks=(2304, 193), leaf=("kw", (5, 193, 8, 128, 256)),
        scopes=DENSE_SCOPES + ROUTED_SCOPES),
    # (K/V belongs to 1 of the 4 layers; the 3 KDA layers' convolution and
    # matrix states and their snapshots are a leaf a layer; every layer
    # routes: no dense `ffn`; 16 decode tiles are under a third of the routed
    # 512, so no token is packed)
    "solar-open2-ep8-4l": dict(
        width=512, blocks=8192, leaf=("k", (1, 8192, 8, 128, 128)),
        scopes=("embed", "attn", "head", "sample") + ROUTED_SCOPES
        + ("moe_shared", "delta_mix", "delta_conv", "delta_scan")),
}


def test_the_model_runs_on_the_launch_width_but_where_tiles_pad_it(
        one_chip, no_persistent_cache, config):
    """`live_width` is the launch's width but where the launch is fleet
    tiles + budget (ISSUE 54, ISSUE 56: every configuration whose full
    fleet's tiles would take a third of the budget's launch): there the
    token-wise layers run on the live tokens alone, the mixed program's
    products see `[live, D]` and none of them the tile layout's width."""
    built, cell = cell_programs(config), CELLS[config]
    live = cell.get("live", cell["width"])
    assert (built.width, built.live) == (cell["width"], live)
    text = built.texts["mixed_step_ragged"]
    cfg = built.cfg
    assert f"[{live},{cfg.dim}]" in text
    if live == built.width:
        return
    # (the tile layout is the kernel's and the head's index space alone: no
    # product's result has its width; what does are the hook's gathers, whole
    # rows of q / k / v on their way to the kernel)
    products = re.findall(
        r"= \w+\[(\d+),[^\]]*\]\S* (?:dot|convolution)\(", text)
    assert products and str(live) in products
    assert str(built.width) not in products
    if config.startswith("granite"):
        assert re.search(rf"f32\[\d+,{live},{live}\]", text)  # the decays
        assert not re.search(rf"f32\[\d+,{built.width},{built.width}\]", text)


def _pool_bytes(pool):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))


def test_the_programs_are_the_cells(one_chip, no_persistent_cache, config):
    from distributed_llm_inference_tpu.engine import paged as EP

    built, cell = cell_programs(config), CELLS[config]
    cfg = built.cfg
    assert set(built.compiled) == STEP_MODULES
    assert (built.width, built.blocks) == (cell["width"], cell["blocks"])
    name, shape = cell["leaf"]
    assert built.pool[name].shape == shape
    if config.startswith("lfm2"):
        assert (len(cfg.conv_layers), len(cfg.attn_layers), cfg.kv_pack) == (7, 2, 2)
    if config.startswith("trinity"):
        assert EP.window_row_budget(cfg.attn_window, built.live, 128) == 37
    if config.startswith("mimo"):
        assert EP.window_row_budget(cfg.attn_window, built.live, 128) == 6
        assert built.pool["k"].shape == (2, 2304, 4, 128, 256)
        assert built.pool["v"].shape == (2, 2304, 4, 128, 128)
        assert built.pool["vw"].shape == (5, 193, 8, 128, 128)
        assert built.pool["routed"].shape == (2, 6, 33)
    if config.startswith("minicpm-sala"):
        assert (len(cfg.attn_layers), len(cfg.linear_layers)) == (4, 12)
        assert built.pool["ck"][0].shape == (9216, 16, 128)
        assert built.pool["lin"][0].shape == (16, 32, 128, 128)
        assert built.pool["snap"][0].shape[1:] == (32, 128, 128)
    if config.startswith("granite"):
        assert (len(cfg.attn_layers), len(cfg.linear_layers), cfg.kv_pack) == (4, 36, 2)
        assert cfg.conv_layers == cfg.linear_layers and not cfg.state_tails
        # (float32 is the configuration's STATED state, and `correct` cannot
        # tell it from bfloat16: this and tests/test_granite_ops.py hold it)
        assert built.pool["lin"][0].shape == (64, 32, 128, 128)
        assert built.pool["snap"][0].shape == (16, 32, 128, 128)
        assert all(a.dtype == "float32" for a in built.pool["lin"] + built.pool["snap"])
        assert built.pool["conv"][0].shape == (64, 3, 4352)
        assert built.pool["csnap"][0].shape == (16, 3, 4352)
    if config.startswith("solar"):
        assert (len(cfg.attn_layers), len(cfg.linear_layers), cfg.kv_pack) == (1, 3, 1)
        assert cfg.conv_layers == cfg.linear_layers == cfg.delta_layers
        # (float32 is the configuration's STATED state: this, the dtype in
        # tests/test_chip_compile.py and tests/test_solar_ops.py's 2e-4 bound
        # hold it, whatever `correct` can tell)
        assert built.pool["lin"][0].shape == (16, 64, 128, 128)
        assert built.pool["snap"][0].shape == (96, 64, 128, 128)
        assert all(a.dtype == "float32" for a in built.pool["lin"] + built.pool["snap"])
        assert built.pool["conv"][0].shape == (16, 3, 24576)
        assert built.pool["csnap"][0].shape == (96, 3, 24576)
        assert all(a.dtype == "bfloat16" for a in built.pool["conv"] + built.pool["csnap"])
        assert built.pool["routed"].shape == (2, 4, 41)
    if cfg.diffusion_block:
        # the decode chunk's flat axis: 32 slots x 2 blocks of 4 = 256 tokens
        assert "bf16[256,2048]" in built.texts["decode_slots_paged"]


# -- the pool is a loop carry that the kernels write in place (ISSUE 29) --------
#
# Before ISSUE 29 each step program held a second pool as a temporary (2.56 /
# 2.97 GB for olmo2's 2.05 GB pool, 2.70 / 3.51 for mistral's 2.27, 2.549 /
# 2.302 for kanana's 2.007) and moved the pool about five times a step; what
# is left is weights relaid out once a launch.

# instructions that make no buffer of their own, or are the kernels
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "custom-call"}


def _pool_sized_instructions(hlo_text, pool):
    """Instructions of a compiled module whose result has the shape of a
    pool leaf or of one layer's slice of it (`copy`, `dynamic-slice`,
    `dynamic-update-slice`, `scatter`, bare or as a fusion's root)."""
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


def _temporaries_bound(config, built):
    """The most a step program's temporaries may hold: nothing of the pool's
    size. lfm2's pool is K/V, the slots' state and the blocks' tails: all
    its temporaries together are smaller than the smallest thing a copy
    could be of: the tails (0.2 GB), a layer's slice of K or V (0.46 GB),
    an expert bank (1.6 GB); the 512-wide mixed step's are 0.11 GB. sdar's
    pool is under a gigabyte and its vocabulary 151,936 wide: the head's
    relayout, once a launch, is 0.62 GB of its 0.86 GB alone (the 32 rows'
    sampling the rest), so its bound is that beside the others' share of
    the pool."""
    if config.startswith("lfm2"):
        return 0.6 * built.pool["tail"].size * 2
    if config.startswith("minicpm-sala"):
        # nothing of the K/V leaves' (1.21 GB each), the snapshot pool's
        # (1.0 GB) or the live states' size (0.4 GB): what is left is the
        # decode chunk's relayout of [4096, 4096] projections, once a launch
        # (34 MB each; PERF.md section 6, PR 48)
        return 0.9 * built.pool["k"].size * 2
    if config.startswith("granite"):
        # nothing of the live states' (4.8 GB), the snapshot pool's (1.2 GB)
        # or a K/V leaf's size (0.27 GB): the mixed step's [64, 640, 640]
        # within-launch decays (105 MB each) and its flat tokens' activations
        return 0.8e9
    bound = 0.45 * _pool_bytes(built.pool)
    if config.startswith("sdar"):
        bound += built.cfg.dim * built.cfg.vocab_size * 2
    return bound


def test_step_programs_hold_no_copy_of_the_pool_at_cell_sizes(
    one_chip, no_persistent_cache, config
):
    built = cell_programs(config)
    pool, pool_bytes = built.pool, _pool_bytes(built.pool)
    assert pool_bytes > CELLS[config].get("pool_bytes", 2e9)
    for name, compiled in built.compiled.items():
        memory = compiled.memory_analysis()
        print(f"{config} {name}: arguments {memory.argument_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {memory.temp_size_in_bytes / 1e6:.1f} MB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.3f} GB of a {pool_bytes / 1e9:.3f} GB pool")
        # the pool goes in and comes out as one buffer ...
        assert memory.alias_size_in_bytes >= pool_bytes - 2**20, (name, memory)
        # ... and the temporaries hold nothing of its size
        assert memory.temp_size_in_bytes < _temporaries_bound(config, built), (
            name, memory)
        assert _pool_sized_instructions(built.texts[name], pool) == [], name
        if config.startswith("trinity"):
            # the sliced head: 25,024 columns are 195.5 lane tiles of 128
            print("head product shapes:", sorted(set(re.findall(
                r"(?:f32|bf16)\[\d+,250(?:24|88)\]", built.texts[name]))))


# -- a linear layer's states go through the scan in place (ISSUE 49) ------------

def test_the_linear_scan_takes_its_leaf_in_place(one_chip, no_persistent_cache,
                                                 config):
    """Both step programs of a configuration whose layers keep a matrix
    state run the scan's kernel once such a layer, under its scope's name
    (what `linear_attn_roofline` / `ssm_scan_roofline` / `delta_scan_roofline`
    find it by), with the
    layer's `lin` leaf as the call's aliased output; no `copy` makes a
    buffer of a state leaf's or the snapshot pool's shape (the test above
    holds the leaves among the program's aliased arguments), and no
    instruction but a row's own `dynamic-update-slice` writes one where a
    snapshot is restored or kept a row at a time
    (models/stack.move_rows)."""
    built = cell_programs(config)
    if not built.cfg.linear_layers:
        assert "lin" not in built.pool
        return
    lin, snap = built.pool["lin"][0], built.pool["snap"][0]
    shapes = {",".join(map(str, leaf.shape)) for leaf in (lin, snap)}
    # (operands: the prefetched scalars, the tokens' blocks, the decay's
    # power, the state: ops/linear_attention.linear_scan, ops/ssm_scan.ssm_scan,
    # ops/delta_rule.delta_state)
    kernel, scope, alias = ("linear_scan", "linear_attn/linear_scan", 8) \
        if built.cfg.sparse_layers else ("ssm_scan", "ssm_mix/ssm_scan", 9)
    # the delta rule's kernel by program: a mixed launch's chunked form, and
    # the decode program's one-token form (ISSUE 58: ops/delta_rule.delta_step,
    # the token's five operands behind two prefetched scalars)
    delta = {"mixed_step_ragged": ("delta_state", 11),
             "decode_slots_paged": ("delta_step", 7)}
    for name, text in built.texts.items():
        if built.cfg.delta_layers:
            scope, (kernel, alias) = "delta_mix/delta_scan", delta[name]
        if kernel == "delta_step":
            # nothing of the chunked form in a program that gives a row one
            # token: not its kernel, not a pair product's `[.., 16, 16, 128]`
            assert not re.search(r"%delta_state[\w.\-]* = ", text)
            assert not re.search(r"f32\[[\d,]*16,16,128\]", text)
        calls = [line for line in text.splitlines()
                 if re.search(rf"%{kernel}[\w.\-]* = .*custom-call\(", line)]
        assert len(calls) == len(built.cfg.linear_layers), (name, len(calls))
        for line in calls:
            assert f"output_to_operand_aliasing={{{{1}}: ({alias}, {{}})}}" in line, line
            assert scope in line, line
        copies = re.findall(r"= f32\[([\d,]+)\]\{[^}]*\} copy\(", text)
        assert not shapes & set(copies), (name, shapes & set(copies))
        if kernel != "linear_scan":
            made = set(re.findall(
                rf"= f32\[(?:{'|'.join(shapes)})\]\{{[^}}]*\}} ([\w\-]+)\(", text))
            assert made <= {"parameter", "get-tuple-element", "bitcast", "tuple",
                            "custom-call", "dynamic-update-slice", "fusion",
                            "while", "conditional"}, (name, made)
    if built.cfg.delta_layers:
        # every layer's call is the SAME lowered kernel (a `jax.jit` of its
        # own: a stack's layers trace and lower it once)
        from dense_equal import canon

        body, kernels = canon(built.texts["decode_slots_paged"])
        bodies = {kernels[int(n) - 1] for n in re.findall(
            r"%delta_step[\w.\-]* = [^\n]*<kernel (\d+)>", body)}
        assert len(bodies) == 1, len(bodies)


# -- the selection scores the compressed keys where they lie (ISSUE 50) ---------

def test_the_selection_gathers_no_table_of_compressed_keys(
        one_chip, no_persistent_cache, config):
    """Both step programs of a configuration with sparse attention layers
    run the scoring's kernel once a sparse layer under the selection's
    scope, each call the SAME lowered kernel (a `jax.jit` of its own: a
    stack's layers trace and lower it once), on the layer's `ck` leaf as it
    lies in the pool; no instruction makes a buffer of a gathered table of
    compressed keys (tiles, or slots, x the table's 4,128 keys x the KV
    heads' 256 numbers: what `pool_ck[held]` and its relayout were), nor of
    the scores over one (`f32[17,8,2,16,4128]`). The six other
    configurations have no such leaf: their programs are the parent's
    (`tests/dense_equal.py --compare-programs`)."""
    from dense_equal import canon

    built = cell_programs(config)
    if not built.cfg.sparse_layers:
        assert "ck" not in built.pool
        return
    cfg, MB = built.cfg, 66048 // 64
    keys = MB * (cfg.sparse_block // cfg.sparse_stride)
    assert keys == 4128 and built.pool["ck"][0].shape == (9216, 16, 128)
    for name, text in built.texts.items():
        calls = [line for line in text.splitlines()
                 if re.search(r"%select_blocks[\w.\-]* = .*custom-call\(", line)]
        assert len(calls) == len(cfg.attn_layers) == 4, (name, len(calls))
        assert all("attn/sparse_select" in line for line in calls)
        assert all("bf16[9216,16,128]" in line for line in calls)
        body, kernels = canon(text)
        bodies = {kernels[int(n) - 1] for n in re.findall(
            r"%select_blocks[\w.\-]* = [^\n]*<kernel (\d+)>", body)}
        assert len(bodies) == 1, (name, len(bodies))
        G = built.width // 8 if name == "mixed_step_ragged" else 16
        gathered = re.findall(
            rf"= \w+\[((?:{G},)?(?:{G * keys}|{keys}|{G},{keys})"
            rf",(?:256|2,128|2,16|2,\d+,128)[\d,]*)\]", text)
        assert gathered == [], (name, sorted(set(gathered)))
        assert not re.search(rf"f32\[{G},\d+,2,16,{keys}\]", text), name


# -- the expert banks ride outside the layer scan (ISSUE 32, 34) ----------------

def _bank_shapes(params):
    """The shapes a copy of a routed expert bank would have: the stacked
    leaf [layers, experts, in, out], or layers x experts flattened."""
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params["layers"])[0]:
        if getattr(path[-1], "key", None) in ("w_gate", "w_up", "w_down") \
                and leaf.ndim == 4:
            layers, experts, a, b = leaf.shape
            shapes |= {f"{layers},{experts},{a},{b}", f"{layers * experts},{a},{b}"}
    return shapes


def test_step_programs_copy_no_expert_bank(one_chip, no_persistent_cache, config):
    """No operation of a bank's size beside the kernel that reads it."""
    built = cell_programs(config)
    shapes = _bank_shapes(built.params)
    if config in ("mistral-7b-16l", "olmo2-7b-16l", "minicpm-sala-9b-16l",
                  "granite-4.0-h-micro"):  # dense: no bank
        assert not built.cfg.n_experts and not shapes
        return
    assert shapes
    for name, text in built.texts.items():
        for shape in shapes:
            assert not re.search(rf"copy\(.*bf16\[{shape}\]", text), (name, shape)


# -- q / k / v and `wo` are read in place (ISSUE 39) ----------------------------

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


def test_step_programs_read_the_attention_projections_in_place(
    one_chip, no_persistent_cache, config
):
    """ISSUE 39: in both step programs, at the cells' sizes and depth (every
    routed or dense stack but kanana's one leading layer longer than one),
    q / k / v (kanana: the query projection) and `wo` are read by their dots
    from the stacked parameter: no slice copy a layer-step, no relayout of a
    stack a launch (`models/llama.pin_products` says what made them).
    The rule is the scanned families'. lfm2's layers are unrolled (two
    kinds) and its attention stack is 2 layers: no slice is copied a
    layer-step there either, but the decode chunk relays the stacks of q,
    k and v out once a launch (25 MB in all)."""
    built = cell_programs(config)
    kinds = {"minicpm-sala-9b-16l": ("sparse", "linear"),
             "granite-4.0-h-micro": ("mamba", "attn")}.get(config)
    if kinds:
        # the family's leaves are a layer's own (models/minicpm_sala.py: no
        # stack to slice or relay out): the rule's shapes do not exist
        # (and a mixer's input projections are one matrix, `w_in`)
        assert all(leaf.ndim == 2 for name in ("w_in", "wo") for kind in kinds
                   for leaf in built.params["layers"][kind][name])
        return
    found = {name: _projection_sized_instructions(text, built.params["layers"])
             for name, text in built.texts.items()}
    if config == "lfm2-24b-a2b-9l":
        assert found["mixed_step_ragged"] == []
        assert all(inst.startswith("copy ") and "[2," in inst
                   for inst in found["decode_slots_paged"]), found
        return
    assert found == {"decode_slots_paged": [], "mixed_step_ragged": []}


# -- the names a trace is read by ----------------------------------------------

def test_step_programs_carry_the_names_a_trace_is_read_by(
    one_chip, no_persistent_cache, config
):
    """Both step programs keep their module names, read the pool through
    the kernel the configuration's file names for them (a block-diffusion
    row is a query tile in both programs: the ragged kernel) and, where the
    experts route, run the grouped kernel, under the family's scopes."""
    built, trace = cell_programs(config), cell_serving(config)["trace"]
    assert set(trace["step_modules"]) == STEP_MODULES
    assert set(trace["attention_kernels"]) == ATTENTION_KERNELS
    experts = trace.get("expert_kernels", [])
    assert set(experts) == (EXPERT_KERNELS if built.cfg.n_experts else set())
    for module, text in built.texts.items():
        kernel = "ragged_paged_attend" if (
            module == "mixed_step_ragged" or built.cfg.diffusion_block
        ) else "paged_flash_attend"
        assert module in module_name(text)
        calls = custom_call_names(text)
        for name in (kernel, *experts):
            assert any(name in c for c in calls), (module, name, sorted(calls))
        stacks = " ".join(set(re.findall(r'op_name="([^"]*)"', text)))
        for scope in set(CELLS[config]["scopes"]) - set(DENSE_SCOPES):
            assert scope in stacks, (module, scope)
        assert_scopes(text, module, CELLS[config]["scopes"])
