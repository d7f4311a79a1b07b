"""Block-level prefix sharing (engine/block_prefix.py + refcounted
BlockAllocator) tests.

The bar: sharing is a MEMORY/ADMISSION strategy, not a semantics change —
a prefix-hit admission that maps shared physical blocks must decode the
exact token stream the cold path decodes; a block mapped by any live
table must never be reclaimed; eviction touches only chains whose every
holder is the index itself; and block accounting must conserve the pool
(free + cached + in-flight == total, shared blocks counted once).
"""

import threading
import time

import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.block_prefix import (
    BlockPrefixIndex, chunk_digests,
)
from distributed_llm_inference_tpu.engine.continuous import (
    ContinuousEngine, _Request,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine

BS = 16  # block size used throughout


# ---------------------------------------------------------------------------
# Allocator refcounts (host-side units, no device work)


def test_allocator_refcounts_and_shared_accounting():
    a = P.BlockAllocator(8)  # 7 usable
    ids = a.alloc(3)
    assert all(a.refcount(b) == 1 for b in ids)
    assert a.shared_blocks == 0
    a.incref(ids[:2])  # a second holder maps two of them
    assert a.refcount(ids[0]) == 2 and a.shared_blocks == 2
    a.decref(ids)  # first holder lets go: only the sole-held block frees
    assert a.free_blocks == 4 + 1
    assert a.refcount(ids[2]) == 0 and a.refcount(ids[0]) == 1
    assert a.shared_blocks == 0
    a.decref(ids[:2])  # last holder: everything back
    assert a.free_blocks == 7
    # free() stays the single-holder spelling (decref)
    ids = a.alloc(7)
    a.free(ids)
    assert a.free_blocks == 7


def test_allocator_alloc_refuses_then_recovers():
    a = P.BlockAllocator(4)
    ids = a.alloc(3)
    assert a.alloc(1) is None
    a.decref(ids)
    assert len(a.alloc(3)) == 3


# ---------------------------------------------------------------------------
# Index units (allocator + index, no device work)


def _ids(n, seed=0):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, 1000, size=n)]


def test_chunk_digests_chain_structure():
    """The affinity-key export (router tier): digests are CHAINED — two
    sequences share digest[i] iff their first (i+1)*chunk items match —
    and only full chunks digest, mirroring lookup()'s partial-tail rule."""
    a = chunk_digests(list(range(40)), 16)
    b = chunk_digests(list(range(16)) + list(range(100, 124)), 16)
    assert len(a) == 2  # 40 // 16 full chunks, partial tail ignored
    assert a[0] == b[0]  # shared first chunk
    assert a[1] != b[1]  # chains diverge at the second chunk
    # chained, not a bag: same chunks in a different order differ at [1]
    c = chunk_digests(list(range(16, 32)) + list(range(16)), 16)
    assert c[0] != a[0] and c[1] != a[1]
    # progressive: a longer head extends, never rewrites, the chain
    assert chunk_digests(list(range(48)), 16)[:2] == a


def test_chunk_digests_bytes_and_str_forms():
    # the router hashes raw prompt text; str and its utf-8 bytes agree
    assert chunk_digests("x" * 130, 64) == chunk_digests(b"x" * 130, 64)
    assert len(chunk_digests("x" * 130, 64)) == 2
    assert chunk_digests("short", 64) == []  # no full chunk, no digest
    assert chunk_digests("", 64) == []
    # max_chunks bounds the walk (router-side cost cap)
    assert len(chunk_digests(b"y" * 1000, 8, max_chunks=4)) == 4
    # token-id and byte forms are distinct key spaces (no cross-collision
    # by construction worth asserting, but both must be stable hex)
    d = chunk_digests([1, 2, 3, 4], 4)
    assert d == chunk_digests([1, 2, 3, 4], 4)
    assert all(isinstance(s, str) and len(s) == 20 for s in d)
    with pytest.raises(ValueError):
        chunk_digests("abc", 0)


def test_index_register_lookup_roundtrip():
    a = P.BlockAllocator(32)
    idx = BlockPrefixIndex(a, BS)
    ids = _ids(3 * BS + 5)
    blocks = a.alloc(4)  # 3 full prompt blocks + decode tail
    idx.register(ids, len(ids), blocks)
    assert idx.stats()["cached_blocks"] == 3  # the partial block never caches

    # identical full prompt: depth capped to leave >= 1 tail token
    p0, shared, key = idx.lookup(ids)
    assert p0 == 3 * BS and shared == blocks[:3]

    # prompt diverging mid-block 2: only the intact full blocks map
    div = list(ids)
    div[BS + 3] += 1
    p0, shared, _ = idx.lookup(div)
    assert p0 == BS and shared == blocks[:1]

    # prompt that IS exactly the cached chain: the last block is
    # recomputed, not mapped (at least one sampling token must prefill)
    p0, shared, _ = idx.lookup(ids[: 3 * BS])
    assert p0 == 2 * BS and shared == blocks[:2]

    assert idx.lookup(_ids(2 * BS, seed=9)) == (0, None, None)


def test_index_register_dedups_existing_chain():
    a = P.BlockAllocator(32)
    idx = BlockPrefixIndex(a, BS)
    ids = _ids(2 * BS + 1)
    b1 = a.alloc(3)
    assert idx.register(ids, len(ids), b1) == 2
    # a second tenant with the same prompt registers its own row whose
    # head MAPS the cached blocks — no new entries, no extra index refs
    b2 = b1[:2] + a.alloc(1)
    assert idx.register(ids, len(ids), b2) == 0
    assert idx.stats()["cached_blocks"] == 2
    assert a.refcount(b1[0]) == 2  # alloc holder + ONE index ref


def test_eviction_only_reclaims_unreferenced_chains():
    a = P.BlockAllocator(32)
    idx = BlockPrefixIndex(a, BS)
    ids = _ids(3 * BS + 1)
    blocks = a.alloc(4)
    idx.register(ids, len(ids), blocks)
    # a live table maps the chain: incref == mapping, as admission does
    a.incref(blocks[:3])
    a.decref(blocks)  # original tenant completes
    assert idx.evict(99) == 0  # every chain block is live-mapped: pinned
    assert idx.stats()["cached_blocks"] == 3
    a.decref(blocks[:3])  # the mapper completes too
    assert idx.evict(99) == 3  # now refcount-1 (index-only): reclaimed
    assert idx.stats()["cached_blocks"] == 0
    assert a.free_blocks == 31


def test_eviction_cascades_root_to_descendants():
    """Evicting an LRU root entry must cascade through its whole subtree:
    a stale child keyed on a recycled parent block id must never revive
    an old chain under new content."""
    a = P.BlockAllocator(32)
    idx = BlockPrefixIndex(a, BS)
    ids = _ids(3 * BS + 1)
    row = a.alloc(4)
    idx.register(ids, len(ids), row)
    a.decref(row)  # tenant completes; chain is index-only
    # the LRU-first entry is the chain's ROOT (registration order):
    # reclaiming one block must take the descendants with it
    assert idx.evict(1) == 3
    assert idx.lookup(ids) == (0, None, None)
    assert idx.stats()["cached_blocks"] == 0
    assert a.free_blocks == 31


def test_divergent_chains_share_root_once():
    """Two chains forking off one shared root block: the root is cached
    once, and draining the cache reclaims every branch exactly once."""
    a = P.BlockAllocator(32)
    idx = BlockPrefixIndex(a, BS)
    head = _ids(BS)
    ids_a = head + _ids(BS, seed=1) + [1]
    ids_b = head + _ids(BS, seed=2) + [2]
    row_a = a.alloc(3)
    idx.register(ids_a, len(ids_a), row_a)
    row_b = [row_a[0]] + a.alloc(2)
    a.incref([row_a[0]])  # chain B maps the shared root
    idx.register(ids_b, len(ids_b), row_b)
    assert idx.stats()["cached_blocks"] == 3  # shared root counted once
    p0, shared, _ = idx.lookup(ids_b)
    assert p0 == 2 * BS and shared == row_b[:2]
    a.decref(row_a)
    a.decref(row_b)
    assert idx.evictable_blocks() == 3
    assert idx.evict(99) == 3
    assert idx.lookup(ids_a) == (0, None, None)
    assert idx.lookup(ids_b) == (0, None, None)
    assert a.free_blocks == 31


# ---------------------------------------------------------------------------
# Engine-level: sharing on the paged fleet


PROMPTS = [
    "the quick brown fox",
    "jumps over",
    "a lazy dog while the band plays on",
    "hello",
]
SHARED = "shared system prefix " * 4  # ~85 byte-fallback tokens


@pytest.fixture(scope="module")
def base_engine():
    cfg = get_model_config("test-llama-tiny")
    return InferenceEngine(
        cfg, engine_cfg=EngineConfig(prefill_buckets=(32, 64))
    )


def _sharing_engine(base, **kw):
    eng = InferenceEngine(
        base.cfg, params=base.backend.params,
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=4
        ),
    )
    args = dict(
        n_slots=2, chunk_steps=4, slot_max_seq=192,
        kv_pool_blocks=40, kv_block_size=BS,
    )
    args.update(kw)
    return ContinuousEngine(eng, **args)


def _submit_all(cont, prompts, **kw):
    out = [None] * len(prompts)

    def run(i):
        out[i] = cont.submit(prompts[i], greedy=True, chat=False, **kw)

    threads = [
        threading.Thread(target=run, args=(i,)) for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


@pytest.mark.slow
def test_hit_vs_cold_bit_exact(base_engine):
    """A prefix-hit admission (mapped shared head + tail prefill) decodes
    the exact greedy text a sharing-free paged fleet decodes — including
    a request whose prompt diverges mid-block."""
    # tails sized so the hit plans INSIDE the 128-token window (a tail
    # past every bucket at the hit offset falls back cold by design)
    mix = [
        SHARED + "first question",
        SHARED + "second question!",
        SHARED[: len(SHARED) // 2] + "diverges mid-stream from the rest",
        "no shared prefix at all",
    ]
    cold = ContinuousEngine(
        base_engine, n_slots=2, chunk_steps=4, slot_max_seq=192,
        kv_pool_blocks=40, kv_block_size=BS,
    )
    try:
        want = [
            cold.submit(p, greedy=True, chat=False, max_tokens=12)
            for p in mix
        ]
    finally:
        cold.close()
    warm = _sharing_engine(base_engine)
    try:
        got = [
            warm.submit(p, greedy=True, chat=False, max_tokens=12)
            for p in mix
        ]
        st = warm.stats()
    finally:
        warm.close()
    for w, g in zip(want, got):
        assert w["status"] == g["status"] == "success"
        assert g["response"] == w["response"]
        assert g["tokens_generated"] == w["tokens_generated"]
    # the full-prefix repeats actually mapped blocks
    assert got[1]["prefix_cached_tokens"] >= BS
    assert got[1]["prefix_cached_tokens"] % BS == 0
    assert got[2]["prefix_cached_tokens"] >= BS  # shared head of SHARED
    assert st["prefix_cache"]["hits"] >= 2
    assert st["prefix_cache"]["dedup_saved_tokens"] >= 2 * BS
    # conservation at idle: every block is free or cached, none leaked
    pg = st["paged"]
    assert pg["free_blocks"] + pg["cached_blocks"] == pg["pool_blocks"] - 1


@pytest.mark.slow
def test_concurrent_sharing_matches_solo(base_engine):
    """Churn: concurrent tenants mapping the same chain (refcount > 1 on
    the head while multiple tables decode off it) still produce the solo
    engine's exact greedy text — a dropped or corrupted shared block
    would diverge some stream."""
    prompts = [SHARED + f"question number {i}" for i in range(6)]
    solo = [
        base_engine.generate(p, greedy=True, chat=False, max_tokens=10)
        for p in prompts
    ]
    warm = _sharing_engine(base_engine, n_slots=3)
    try:
        got = _submit_all(warm, prompts, max_tokens=10)
        st = warm.stats()
    finally:
        warm.close()
    for w, g in zip(solo, got):
        assert g["status"] == "success"
        assert g["response"] == w["response"]
    assert st["prefix_cache"]["hits"] >= 1
    pg = st["paged"]
    assert pg["free_blocks"] + pg["cached_blocks"] == pg["pool_blocks"] - 1


@pytest.mark.slow
def test_pool_exhaustion_with_shared_blocks_resident(base_engine):
    """A pool too small to hold a new worst-case tenant PLUS the resident
    cached chains still serves everything: admission evicts unreferenced
    chains (never live-mapped ones) instead of deadlocking on a free list
    the cache has eaten."""
    # slot class 96 -> 6 blocks worst case; 9 usable blocks. Each ~57-token
    # prompt caches 3 full blocks on completion, so by the third DISTINCT
    # prompt the cache holds 6 of the 9 blocks and admission must reclaim.
    longs = [f"p{i} " * 18 + "end" for i in range(3)]
    warm = _sharing_engine(
        base_engine, n_slots=2, slot_max_seq=96, kv_pool_blocks=10,
    )
    try:
        solo = [
            base_engine.generate(p, greedy=True, chat=False, max_tokens=30)
            for p in longs
        ]
        got = [
            warm.submit(p, greedy=True, chat=False, max_tokens=30)
            for p in longs
        ]
        st = warm.stats()
    finally:
        warm.close()
    for w, g in zip(solo, got):
        assert g["status"] == "success"
        assert g["response"] == w["response"]
    pg = st["paged"]
    assert pg["free_blocks"] + pg["cached_blocks"] == pg["pool_blocks"] - 1
    # the cache had to give blocks back at least once
    assert st["prefix_cache"]["evictions"] >= 1
    # concurrency on top: live-mapped chains stay pinned while the pool
    # churns, and every stream still matches solo
    solo2 = [
        base_engine.generate(p, greedy=True, chat=False, max_tokens=40)
        for p in PROMPTS
    ]
    warm2 = _sharing_engine(
        base_engine, n_slots=4, slot_max_seq=96, kv_pool_blocks=10,
    )
    try:
        got2 = _submit_all(warm2, PROMPTS, max_tokens=40)
        st2 = warm2.stats()
    finally:
        warm2.close()
    for w, g in zip(solo2, got2):
        assert g["status"] == "success"
        assert g["response"] == w["response"]
    pg2 = st2["paged"]
    assert pg2["free_blocks"] + pg2["cached_blocks"] == pg2["pool_blocks"] - 1


@pytest.mark.slow
def test_blocked_release_frees_granted_blocks(base_engine):
    """Regression for the admission pool-block leak: blocks granted, then
    `_BLOCKED` on constraint-table backpressure must decref the grant —
    a retry re-allocates, and the first grant would otherwise be orphaned
    (refcount 1, no holder, never freed)."""
    warm = _sharing_engine(base_engine)
    total = warm._alloc.n_blocks - 1
    real_acquire = warm._ctable.acquire
    calls = []

    def acquire_once_blocked(art):
        calls.append(warm._alloc.free_blocks)
        if len(calls) == 1:
            return None  # simulate a full constraint table
        return real_acquire(art)

    warm._ctable.acquire = acquire_once_blocked
    try:
        req = _Request(
            "hello there",
            dict(max_tokens=6, greedy=True, chat=False,
                 constraint={"choices": ["aa", "bb"]}),
        )
        assert warm._enqueue(req) is None
        assert req.done.wait(timeout=120)
        assert req.result["status"] == "success"
        # free at the SECOND acquire (post-retry re-grant) must equal free
        # at the first — a leak would show the retry eating a second grant
        assert len(calls) >= 2
        assert calls[1] == calls[0]
        # drain: nothing in flight keeps blocks; only the cache may hold
        deadline = time.time() + 10
        while time.time() < deadline:
            pg = warm.stats()["paged"]
            if pg["free_blocks"] + pg["cached_blocks"] == total:
                break
            time.sleep(0.05)
        pg = warm.stats()["paged"]
        assert pg["free_blocks"] + pg["cached_blocks"] == total
    finally:
        warm._ctable.acquire = real_acquire
        warm.close()


@pytest.mark.slow
def test_sharing_disabled_without_prefix_entries(base_engine):
    """prefix_cache_entries=0 keeps the paged fleet sharing-free: no
    index, full free list after completion (the pre-sharing contract)."""
    cont = ContinuousEngine(
        base_engine, n_slots=2, chunk_steps=4, slot_max_seq=96,
        kv_pool_blocks=16, kv_block_size=BS,
    )
    try:
        out = cont.submit(SHARED + "q", greedy=True, chat=False,
                          max_tokens=8)
        assert out["status"] == "success"
        assert "prefix_cached_tokens" not in out
        st = cont.stats()
    finally:
        cont.close()
    assert cont._bpx is None
    assert st["paged"]["free_blocks"] == 15
    assert "prefix_cache" not in st


@pytest.mark.slow
def test_pp_block_sharing_matches_dense(eight_devices):
    """Block sharing on the pp=2 mesh: the ragged launches that attend the
    mapped head in place compose with the gated ring — hit streams match
    the solo pp engine exactly."""
    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.runtime import create_engine

    mix = [SHARED + "first question", SHARED + "second question!"]
    eng = create_engine(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=4
        ),
    )
    # solo pp path as the reference stream (solo-vs-fleet greedy parity
    # is the structural contract every fleet test leans on)
    want = [
        eng.generate(p, greedy=True, chat=False, max_tokens=10)
        for p in mix
    ]
    warm = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, slot_max_seq=128,
        kv_pool_blocks=24, kv_block_size=BS,
    )
    try:
        got = [
            warm.submit(p, greedy=True, chat=False, max_tokens=10)
            for p in mix
        ]
    finally:
        warm.close()
    for w, g in zip(want, got):
        assert w["status"] == g["status"] == "success"
        assert g["response"] == w["response"]
    assert got[1]["prefix_cached_tokens"] >= BS
