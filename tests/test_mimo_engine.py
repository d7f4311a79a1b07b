"""The mimo_v2 family through the served engine (ISSUE 55):
`engine/continuous.py` over a pool grouped by layer kind whose groups' rows
are their own kinds' (test-mimo-tiny: keys of 192 numbers on 256 lanes and
values of 128, 1 K/V head in the global layers and 2 in the window ones, a
sink a query head, window 16 at blocks of 8, a share of 4 of the 8 experts),
seeded random weights, float32. Every token the fleet delivers is held
against the plain reference's LOGITS (its margin below the reference's best,
in logit-sigmas; 2e-5: the tolerance of tests/test_mimo.py, for its reason):
eight rows at once of different lengths, chunked prefill beside decode rows,
a decode long enough that a row gives back window blocks INSIDE its decode
chunks and the blocks are let to other rows while it still decodes, a prefix
hit deeper than a window; the window group's turnover in the counters and in
the launch records; what a grouped pool cannot take, refused at start-up.
"""

import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, create_engine
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.models.registry import get_model_config, register

import test_afmoe_engine as afmoe_engine
from mimo_util import ref_logits
from test_afmoe_engine import prompt_ids

SEED, BS, TOL = 3, 8, 2e-5
MODEL = register(get_model_config("test-mimo-tiny").replace(
    name="test-mimo-share", expert_lo=2, n_experts_held=4))


class Fleet(afmoe_engine.Fleet):
    model, block, ref = MODEL.name, BS, staticmethod(ref_logits)


_FLEETS = {}


def fleet(**kw):
    key = tuple(sorted(kw.items()))
    if key not in _FLEETS:
        _FLEETS[key] = Fleet(**kw)
    return _FLEETS[key]


@pytest.fixture(scope="module", autouse=True)
def _close_fleets():
    yield
    for f in _FLEETS.values():
        f.ce.close()
    _FLEETS.clear()


# eight rows for eight slots at once, prompts of 5-70 tokens (up to four
# windows) at a 96-token step (the fleet's 8 tiles and 32 for prefill), then
# decoded 12-44 tokens in chunks of 4 steps: the longest decodes cross five
# block edges, each inside a run of chunks dispatched ahead of their fetch
ASKS = [(20, 44), (21, 12), (33, 40), (5, 30), (45, 28), (70, 26), (9, 36), (58, 14)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_eight_rows_agree_with_the_reference_across_given_back_blocks(impl):
    f = fleet(impl=impl, slots=8, budget=96, pool=120)
    asks = [(prompt_ids(n), mt) for n, mt in ASKS]
    res = f.ask_all(asks)
    for (ids, mt), r in zip(asks, res):
        assert 0 < len(r["ids"]) <= mt  # (greedy may choose eos and end early)
        assert f.margins(ids, r).max() < TOL, (len(ids), f.margins(ids, r))
    wg = f.ce._wgrp
    # window 16 at blocks of 8 under a 96-token launch: 15 blocks a row; the
    # group is the slots' budgets and its null block
    assert wg.row_budget == 15 and wg.alloc.n_blocks == 8 * 15 + 1
    assert max(np.count_nonzero(wg.table, axis=1)) <= wg.row_budget
    # the turnover: counted on the host, in the counters and launch by launch
    assert wg.given > wg.released > 3 * 8
    assert f.series("dli_kv_window_blocks_released_total")[()] == wg.released
    assert f.series("dli_kv_window_blocks_given_total")[()] == wg.given
    turned = [r for r in f.records if "window_blocks_given" in r]
    assert len(turned) == len(f.records)
    assert sum(r["window_blocks_given"] for r in turned) == wg.given
    assert sum(r["window_blocks_released"] for r in turned) == wg.released
    chunks = [r for r in turned if r["phase"] == "chunk"]
    # rows give blocks back INSIDE decode chunks: at least three each for the
    # rows that decode 40 tokens and more
    assert sum(r["window_blocks_released"] for r in chunks) >= 3 * 2
    assert sum(r["window_blocks_given"] for r in chunks) >= 3 * 2
    # a block of either group holds the same positions and not the same
    # bytes: 2 layers x 1 head x 8 x (256 + 128) x 4 B, and 2 x 2 heads
    sizes = f.series("dli_kv_group_block_bytes")
    assert sizes[(("group", "global"),)] == 2 * 1 * 8 * 384 * 4
    assert sizes[(("group", "window"),)] == 2 * 2 * 8 * 384 * 4
    kinds = [r for r in f.records if "kv_tokens_window" in r]
    assert kinds and all(
        r["kv_tokens"] == 2 * r["kv_tokens_global"] + 2 * r["kv_tokens_window"]
        for r in kinds)
    bs = f.ce.kv_block_size
    assert all((0 < r["kv_walk_steps"] <= r["kv_grid_tokens"] // bs)
               if impl == "pallas" else r["kv_walk_steps"] == 0 for r in kinds)
    pairs = f.series("dli_moe_pairs_total")
    assert 0 < pairs[(("where", "held"),)] < pairs[(("where", "routed"),)]
    stats = f.ce.stats()["paged"]["window_group"]
    assert (stats["given_blocks"], stats["released_blocks"]) == (wg.given, wg.released)
    assert f.books_balance()


def test_given_back_blocks_are_let_again_while_the_row_decodes():
    """Two slots over a window group of two rows' budgets (7 blocks each at a
    32-token launch): six rows write 60 blocks through it, so a block a row
    gave back inside a decode chunk is written by the other row, or the next
    tenant, while the first still decodes; a block written while a
    dispatched launch still read it would show in the margins."""
    f = fleet(budget=32, pool=64)
    asks = [(prompt_ids(n, salt=3), mt) for n, mt in
            [(20, 60), (33, 50), (5, 44), (45, 30), (70, 40), (12, 52)]]
    res = f.ask_all(asks)
    for (ids, _), r in zip(asks, res):
        assert f.margins(ids, r).max() < TOL
    wg = f.ce._wgrp
    assert wg.alloc.n_blocks == 2 * wg.row_budget + 1 == 15
    assert wg.released > 2 * wg.alloc.n_blocks  # given back, and let again
    assert max(np.count_nonzero(wg.table, axis=1)) <= wg.row_budget
    assert f.books_balance()


def test_a_prefix_hit_deeper_than_a_window_serves_what_a_cold_prefill_serves():
    """The second ask shares 48 tokens (6 blocks, three windows) with the
    first, whose row gave most of its window blocks back while it went on:
    the index kept them, the hit maps the global group's 6 blocks and the
    window group's last 2 (the window's 15 positions below depth 48 overlap
    blocks 4 and 5), and the tokens are the cold fleet's, all the
    reference's."""
    doc = prompt_ids(48, salt=7)
    a, b = doc + prompt_ids(5, salt=8), doc + prompt_ids(6, salt=9)
    f = fleet(budget=32, pool=64)
    ra, = f.ask_all([(a, 6)])
    rb, = f.ask_all([(b, 7)])
    assert not ra.get("prefix_cached_tokens") and rb["prefix_cached_tokens"] == 48
    assert f.series("dli_prefix_hits_total")[(("window", "resident"),)] >= 1
    cold = fleet(budget=32, pool=64, slots=1)  # (another fleet: nothing cached)
    rc, = cold.ask_all([(b, 7)])
    assert not rc.get("prefix_cached_tokens") and rc["ids"] == rb["ids"]
    assert f.margins(b, rb).max() < TOL
    assert f.books_balance()


@pytest.mark.parametrize("kw,what", [
    (dict(kv_shadow=True), "host shadow store"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(chunked_prefill=False), "unchunked ragged admission"),
])
def test_what_a_grouped_pool_does_not_carry_is_refused_at_start_up(kw, what):
    kw = dict(kw)
    ecfg = {k: kw.pop(k) for k in ("spec_decode", "chunked_prefill") if k in kw}
    eng = create_engine(MODEL.name, seed=SEED, dtype="float32",
                        engine_cfg=EngineConfig(prefix_cache_entries=8, **ecfg))
    args = dict(n_slots=2, kv_pool_blocks=48, kv_block_size=BS,
                kv_shadow=kw.pop("kv_shadow", False), slot_max_seq=160)
    with pytest.raises(ValueError, match=what):
        ContinuousEngine(eng, **args)


@pytest.mark.parametrize("what,kw", [
    ("meshes", dict(mesh=True)), ("quantization", dict(quant="int8")),
    ("int8 pool", dict(kv_quant="int8")), ("LoRA", dict(lora="dir")),
])
def test_a_mesh_quantization_and_lora_stay_refused_by_name(what, kw):
    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.engine import paged as P

    if "mesh" in kw:
        with pytest.raises(ValueError, match=what):
            create_engine(MODEL.name, seed=SEED, dtype="float32",
                          mesh_cfg=MeshConfig(pp=2))
        return
    with pytest.raises(ValueError, match=what):
        P.refuse_unsupported_latent(get_model_config(MODEL.name), **kw)


# -- the window group under a launch of fleet tiles + budget (ISSUE 56) --------

@pytest.mark.parametrize("config,slots,blocks,widths,budget,groups", [
    # the launch's 768 tile places would make a row's budget 8 blocks and the
    # group 257 for nothing: one row carries at most the axis's 512 a launch
    ("mimo-v2.5-7l", 32, 2304, (768, 512), 6, (2304, 193)),
    ("trinity-large-ep8-5l", 16, 4608, (512, 512), 37, (4608, 1152)),
])
def test_a_window_group_is_sized_from_the_axis_the_model_computes(
        config, slots, blocks, widths, budget, groups):
    from dense_equal import cell_config
    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.engine.scheduler import live_width, step_width

    cfg, served = cell_config(config)
    assert served == slots
    assert (step_width(cfg, slots, 8), live_width(cfg, slots, 8)) == widths
    row = P.window_row_budget(cfg.attn_window, widths[1], 128)
    assert row == budget
    assert P.group_blocks(cfg, blocks, row, slots, 128) == groups


def test_the_engine_sizes_the_window_group_from_the_compact_axis_and_serves_it():
    """test-mimo-tiny at 24 slots and no explicit budget: the fleet's 192 tile
    places on top of the routed 512, of which the model computes 512. The
    window group is the slots' budgets at a launch of 512 tokens a row (not
    704), and the rows it serves on the compact axis agree with the reference
    as the tile layout's do."""
    f = fleet(impl="pallas", slots=24, budget=None, pool=120)
    sched = f.ce.stats()["scheduler"]
    assert (sched["step_width"], sched["live_width"]) == (24 * 8 + 512, 512)
    wg = f.ce._wgrp
    assert wg.row_budget == -(-(16 + 512) // BS) + 1 == 67
    assert f.ce._group_blocks == (120, 24 * 67 + 1)
    asks = [(prompt_ids(n), mt) for n, mt in ASKS[:5]]
    for (ids, mt), r in zip(asks, f.ask_all(asks)):
        assert 0 < len(r["ids"]) <= mt
        assert f.margins(ids, r).max() < TOL, (len(ids), f.margins(ids, r))
    mixed = [r for r in f.records if r["phase"] == "mixed"]
    assert mixed and all(r["tokens_computed"] == 512 for r in mixed)
    assert all(r["tiles"] == (24 * 8 + 512) // 8 for r in mixed)
    assert any(r["decode_rows"] and r["prefill_chunks"] for r in mixed)
    assert f.books_balance()
