"""The afmoe family through the served engine (ISSUE 40): `engine/continuous.py`
over a pool grouped by layer kind at `test-trinity-tiny` (window 8, blocks of
4, a share of 4 of the 8 experts), seeded random weights, float32. Every
token the fleet delivers is held against the plain reference's LOGITS (its
margin below the reference's best, in logit-sigmas; 2e-5: the tolerance of
tests/test_afmoe.py, for its reason): chunked prefill beside decode rows over
sequences several windows long, window blocks given back and let to another
row while the first still decodes, a prefix hit deeper than a window, a hit
whose window blocks were evicted; the allocator's invariants under random
traffic; the counters and launch-record fields the benchmark reads; and
what a grouped pool cannot take, refused at start-up with a message.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, create_engine
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.block_prefix import BlockPrefixIndex
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.models.registry import get_model_config, register

from afmoe_util import ref_logits

SEED, BS, TOL = 3, 4, 2e-5
MODEL = register(get_model_config("test-trinity-tiny").replace(
    name="test-trinity-share", expert_lo=2, n_experts_held=4))


class WordTok:
    """Token i is the word w<i>: prompts and answers ARE their ids."""

    def encode(self, text):
        return [int(w[1:]) for w in text.split()]

    def decode(self, ids, skip_special_tokens=True):
        return " ".join(f"w{int(i)}" for i in ids)


def words(ids):
    return " ".join(f"w{i}" for i in ids)


def prompt_ids(n, salt=0):
    return [int(t) for t in np.random.default_rng(1000 * salt + n).integers(3, 250, n)]


class Fleet:
    """A served fleet of a grouped-pool family (tests/test_mimo_engine.py
    serves its own model and reference through it)."""
    model, block, ref = MODEL.name, BS, staticmethod(ref_logits)

    def __init__(self, impl="xla", budget=16, slots=2, pool=48, chunk=4, **kw):
        self.eng = create_engine(
            self.model, seed=SEED, attn_impl=impl, dtype="float32",
            engine_cfg=EngineConfig(prefix_cache_entries=8, step_token_budget=budget))
        self.eng.tokenizer = WordTok()
        self.ce = ContinuousEngine(
            self.eng, n_slots=slots, chunk_steps=chunk, kv_pool_blocks=pool,
            kv_block_size=self.block, kv_shadow=False, slot_max_seq=160, **kw)
        self.cfg = self.eng.cfg
        self.records = []
        record = self.ce._launch_record
        self.ce._launch_record = lambda *a, **k: self.records.append(record(*a, **k)) \
            or self.records[-1]

    def ask_all(self, asks):
        out = [None] * len(asks)

        def one(i, ids, mt):
            out[i] = self.ce.submit(words(ids), max_tokens=mt, greedy=True, chat=False)

        ts = [threading.Thread(target=one, args=(i, *a)) for i, a in enumerate(asks)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(600)
        for r in out:
            assert r is not None and r.get("status") == "success", r
            r["ids"] = WordTok().encode(r["response"]) if r["response"] else []
        return out

    def margins(self, prompt, res):
        seq = prompt + res["ids"]
        lg = self.ref(self.cfg, SEED, seq)[len(prompt) - 1:len(seq) - 1]
        chosen = np.asarray(res["ids"])
        return (lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen]) / lg.std()

    def series(self, name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in self.eng.metrics.snapshot().get(name, {}).get("series", [])}

    def books_balance(self):
        """Nothing held once every row has gone and the index is cleared."""
        ce = self.ce
        ce._bpx.clear()
        return ce._alloc.outstanding == 0 and ce._wgrp.alloc.outstanding == 0 \
            and not ce._wgrp.table.any() and ce._wgrp.reserved() == 0


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


# six rows for two slots, up to nine windows long (70 tokens at a window of
# 8), at a 16-token step budget: chunks share launches with decode rows,
# every slot is let again, and the window group (19 blocks: two rows'
# budgets of 9 and the null block) is far smaller than what the rows write
# (45 blocks), so blocks a row gave back are let to the other row, and to
# the next tenant, while the first still decodes
ASKS = [(20, 14), (21, 9), (33, 12), (5, 10), (45, 8), (70, 6)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_served_rows_agree_with_the_reference_over_given_back_blocks(impl):
    f = fleet(impl=impl)
    asks = [(prompt_ids(n), mt) for n, mt in ASKS]
    res = f.ask_all(asks)
    for (ids, mt), r in zip(asks, res):
        assert len(r["ids"]) == mt or f.cfg.eos_token_id in r["ids"][-1:] or len(r["ids"]) > 0
        assert f.margins(ids, r).max() < TOL, (len(ids), f.margins(ids, r))
    wg = f.ce._wgrp
    assert wg.alloc.n_blocks == 2 * wg.row_budget + 1 == 19  # (< pool / 4 is the floor)
    assert wg.released > wg.alloc.n_blocks  # given back, and let again
    assert f.series("dli_kv_window_blocks_released_total")[()] == wg.released
    assert max(np.count_nonzero(wg.table, axis=1)) <= wg.row_budget
    kinds = [r for r in f.records if "kv_tokens_window" in r]
    assert kinds and all(
        r["kv_tokens"] == r["kv_tokens_global"] + 4 * r["kv_tokens_window"]
        and r["kv_grid_tokens"] == r["kv_grid_tokens_global"] + 4 * r["kv_grid_tokens_window"]
        for r in kinds)
    # a decode row at position p reads min(p + 1, 8) positions a window layer
    assert any(r["kv_tokens_window"] < r["kv_tokens_global"] for r in kinds)
    # the walk's loop steps, the kinds summed by their layers like the pages:
    # none under the gather path, at most one a page under the kernels
    bs = f.ce.kv_block_size
    assert all((0 < r["kv_walk_steps"] <= r["kv_grid_tokens"] // bs)
               if impl == "pallas" else r["kv_walk_steps"] == 0 for r in kinds)
    f.ce._note_groups(every=0.0)
    groups = f.series("dli_kv_group_blocks")
    for group, alloc in (("global", f.ce._alloc), ("window", wg.alloc)):
        states = {s: groups[(("group", group), ("state", s))]
                  for s in ("live", "cached", "free")}
        assert sum(states.values()) == alloc.n_blocks - 1 and states["cached"] > 0, states
        assert states["live"] == 0  # every row has gone
    pairs = f.series("dli_moe_pairs_total")
    held, routed = pairs[(("where", "held"),)], pairs[(("where", "routed"),)]
    assert 0 < held < routed  # a share: half the experts live here
    assert f.books_balance()


def test_a_prefix_hit_deeper_than_a_window_serves_what_a_cold_prefill_serves():
    """(c) through the allocator: the second ask shares 48 tokens (12 blocks,
    six windows) with the first, whose row gave most of its window blocks
    back while it went on: the index kept them, the hit maps the global
    group's 12 blocks and the window group's last 2, and the tokens are the
    cold fleet's, all the reference's."""
    doc = prompt_ids(48, salt=7)
    a, b = doc + prompt_ids(5, salt=8), doc + prompt_ids(6, salt=9)
    f = fleet(pool=64)
    ra, = f.ask_all([(a, 6)])
    rb, = f.ask_all([(b, 7)])
    assert not ra.get("prefix_cached_tokens") and rb["prefix_cached_tokens"] == 48
    assert f.series("dli_prefix_hits_total")[(("window", "resident"),)] == 1
    cold = fleet(pool=64, slots=1)  # (another fleet: nothing cached)
    rc, = cold.ask_all([(b, 7)])
    assert not rc.get("prefix_cached_tokens") and rc["ids"] == rb["ids"]
    assert f.margins(b, rb).max() < TOL
    # evicted: the window group's cached blocks go, the global chain stays;
    # the next ask finds the chain, not its window, starts cold and is exact
    assert f.ce._bpx.evict_side(1000) > 0
    c = doc + prompt_ids(7, salt=10)
    rc2, = f.ask_all([(c, 5)])
    assert not rc2.get("prefix_cached_tokens")
    assert f.series("dli_prefix_hits_total")[(("window", "evicted"),)] == 1
    assert f.margins(c, rc2).max() < TOL
    # and what the cold prefill registered serves the next hit again
    rd, = f.ask_all([(doc + prompt_ids(4, salt=11), 4)])
    assert rd["prefix_cached_tokens"] == 48
    assert f.books_balance()


def test_a_hit_is_shortened_to_the_deepest_depth_whose_window_is_resident():
    """Only the deepest blocks' window blocks go: the hit falls back to the
    deepest depth that still has its whole window."""
    alloc, side = P.BlockAllocator(64), P.BlockAllocator(64)
    idx = BlockPrefixIndex(alloc, BS, side=side, window=8)
    ids = prompt_ids(41)
    g, w = alloc.alloc(10), side.alloc(10)
    idx.register(ids, 40, g, side_blocks=dict(enumerate(w)))
    assert idx.lookup(ids)[0] == 40 and idx.side_blocks(g) == (8, w[8:])
    side.decref(w)  # the row has gone: the index holds them alone
    gone = idx._side_of.pop(g[9]); side.decref([gone])
    p0, blocks, _ = idx.lookup(ids)
    assert p0 == 36 and idx.side_blocks(blocks) == (7, w[7:9])
    gone = idx._side_of.pop(g[7]); side.decref([gone])
    assert idx.lookup(ids)[0] == 28  # depths 8 and 9 need block 7
    alloc.decref(g)
    assert idx.evict(100) == 10  # an entry takes its window block with it
    assert alloc.outstanding == 0 and side.outstanding == 0


def test_the_allocators_hold_their_invariants_under_random_traffic():
    """(f): random admit / advance / release / evict over the two groups and
    the index: no block in two owners, a row never over its budget, the
    promise to admitted rows never broken, refcounts back to zero."""
    rng = np.random.default_rng(0)
    n_slots, MB, W, launch = 3, 64, 8, 6
    alloc = P.BlockAllocator(200)
    wg = P.WindowBlocks(12, n_slots, MB, BS, W, launch)
    idx = BlockPrefixIndex(alloc, BS, side=wg.alloc, window=W)
    wg.index = idx
    docs = [prompt_ids(int(rng.integers(20, 120)), salt=s) for s in range(5)]
    rows = {}  # slot -> dict(ids, pos, blocks, resume)
    admitted = refused = 0
    for _ in range(1500):
        slot = int(rng.integers(n_slots))
        row = rows.get(slot)
        if row is None:
            ids = docs[int(rng.integers(len(docs)))] + prompt_ids(3, salt=int(rng.integers(99)))
            p0, shared, key = idx.lookup(ids)
            idx.mark(key, bool(p0), p0)
            shared = list(shared or [])
            need = -(-len(ids) // BS)
            alloc.incref(shared)
            if not wg.admit(slot, need, *(idx.side_blocks(shared) if shared else (0, []))):
                alloc.decref(shared)
                refused += 1
                continue
            fresh = alloc.alloc(need - len(shared))
            assert fresh is not None
            rows[slot] = dict(ids=ids, pos=p0, blocks=shared + fresh, resume=None)
            admitted += 1
        elif row["pos"] >= len(row["ids"]) or rng.random() < 0.05:
            wg.release_row(slot)  # done, or cancelled midway
            alloc.decref(row["blocks"])
            del rows[slot]
        else:
            n = min(int(rng.integers(1, launch + 1)), len(row["ids"]) - row["pos"])
            wg.ensure(slot, row["pos"], n)
            row["pos"] += n
            idx.register(row["ids"], row["pos"], row["blocks"],
                         side_blocks=dict(wg.held(slot)), resume=row["resume"])
            row["resume"] = idx.resume
            wg.release_below(slot, row["pos"] - 1)
        if rng.random() < 0.03:
            idx.evict(int(rng.integers(1, 20)))
        # the invariants
        held = [b for s in rows for _, b in wg.held(s)]
        cached = set(idx._side_of.values())
        owners = {}
        for s in rows:
            for _, b in wg.held(s):
                owners.setdefault(b, []).append(s)
        for b, who in owners.items():  # shared only through the index
            assert len(who) == 1 or b in cached
            assert wg.alloc.refcount(b) == len(who) + (b in cached)
        assert all(wg.alloc.refcount(b) >= 1 for b in cached)
        assert wg.alloc.outstanding == len(set(held) | cached)
        assert all(len(wg.held(s)) <= wg.row_budget for s in rows)
        assert wg.alloc.free_blocks + idx.side_evictable() >= wg.reserved()
        for s, row in rows.items():  # every position a next query reads is held
            lo = max(0, row["pos"] - W + 1) // BS
            assert all(wg.table[s, b] for b in range(lo, -(-row["pos"] // BS)))
    assert admitted > 50 and refused > 0 and wg.released > 100
    for slot, row in rows.items():
        wg.release_row(slot)
        alloc.decref(row["blocks"])
    idx.clear()
    assert alloc.outstanding == 0 and wg.alloc.outstanding == 0
    assert wg.reserved() == 0 and not wg.table.any()


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
    args = dict(n_slots=2, kv_pool_blocks=kw.pop("pool", 48), kv_block_size=BS,
                kv_shadow=kw.pop("kv_shadow", False), slot_max_seq=160)
    with pytest.raises(ValueError, match=what):
        ContinuousEngine(eng, **args)


def test_a_mesh_for_a_routed_share_stays_refused():
    from distributed_llm_inference_tpu import MeshConfig

    with pytest.raises(ValueError, match="meshes"):
        create_engine(MODEL.name, seed=SEED, dtype="float32", mesh_cfg=MeshConfig(pp=2))
