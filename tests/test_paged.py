"""Block-paged KV cache (engine/paged.py) tests.

The bar: paged mode is a MEMORY strategy, not a semantics change — every
token stream must be bit-identical to the dense fleet's (greedy, fp32),
while fleet HBM becomes a function of the pool and admission backpressures
on pool exhaustion instead of over-allocating.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine

from paged_walk_cases import DECODE_BLOCK_CASES, check_decode_block_case

PROMPTS = [
    "the quick brown fox",
    "jumps over",
    "a lazy dog while the band plays on",
    "hello",
]


@pytest.fixture(
    scope="module", params=["test-llama-tiny", "test-gpt2-tiny"]
)
def solo_engine(request):
    # BOTH families: the paged pool rides the shared attn_hook seam
    # (gpt2's block routes through llama.default_attn_hook since round
    # 5), so every fleet-level test here runs against each
    cfg = get_model_config(request.param)
    return InferenceEngine(
        cfg, engine_cfg=EngineConfig(prefill_buckets=(32, 64))
    )


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


def test_allocator():
    a = P.BlockAllocator(8)  # 7 usable (block 0 is trash)
    assert a.free_blocks == 7
    ids = a.alloc(5)
    assert len(ids) == 5 and 0 not in ids
    assert a.alloc(3) is None  # only 2 left
    more = a.alloc(2)
    assert a.free_blocks == 0
    a.free(ids)
    assert a.free_blocks == 5
    assert sorted(a.alloc(5)) == sorted(ids)
    a.free(more)
    with pytest.raises(ValueError):
        P.BlockAllocator(1)


def test_blocks_needed():
    assert P.blocks_needed(8, 8, 16) == 1
    assert P.blocks_needed(9, 8, 16) == 2
    assert P.blocks_needed(16, 16, 16) == 2
    assert P.blocks_needed(1, 1, 16) == 1


def _fill_slot_paged(backend, pool, state, sparams, slot, row, tokens, plen,
                     key, sampling, max_tokens, knobs):
    """What a paged fleet's admission runs: ONE ragged launch prefills
    tokens[0, :plen] straight into `row`'s pool blocks and samples the
    first token off the last one, then the slot's state is armed.
    Returns (first, pool, state, sparams)."""
    n = int(plen)
    meta, tok_row, tok_pos, _, _ = P.build_ragged_meta(
        [(0, 0, n, P.RAGGED_PREFILL)], width=tokens.shape[1], tile=8,
    )
    first, _, pool = backend.prefill_ragged_paged(
        tokens[0], jnp.asarray(tok_row), jnp.asarray(tok_pos),
        jnp.asarray(meta), pool, jnp.asarray(row)[None, :],
        jnp.int32(n - 1), key, sampling,
    )
    state, sparams = backend.arm_slot_paged(
        state, sparams, slot, first[0], plen, max_tokens, *knobs
    )
    return first, pool, state, sparams


@pytest.mark.slow
def test_decode_slots_paged_matches_dense(solo_engine):
    """Device-level: one occupied slot decoding over the block pool emits
    the exact stream the dense fleet emits from the same prefill."""
    eng = solo_engine
    cfg = eng.cfg
    backend = eng.backend
    sampling = G.default_sampling(greedy=True)
    key = jax.random.PRNGKey(7)
    tokens = jnp.asarray(
        [[cfg.bos_token_id, 11, 12, 13, 14, 15, 16, 17]], jnp.int32
    )
    tokens = jnp.pad(tokens, ((0, 0), (0, 24)), constant_values=cfg.pad_token_id)
    plen, n_slots, steps = jnp.int32(8), 4, 12
    bs = 8
    MB = 4  # logical window 32
    knobs = (
        jnp.float32(1.0), jnp.int32(0), jnp.float32(1.0), True,
        jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(0.0), jnp.float32(0.0),
        jnp.zeros((cfg.vocab_size,), bool),
    )

    # dense fleet
    scratch = backend.init_cache(1, MB * bs)
    first, _, scratch = backend.prefill(tokens, plen, scratch, key, sampling)
    state, sparams = G.init_slots(n_slots, cfg.vocab_size)
    cache = backend.init_cache(n_slots, MB * bs)
    cache, state, sparams = G.insert_slot(
        cfg, cache, scratch, state, sparams, 1, first[0], plen,
        jnp.int32(steps + 1), *knobs,
    )
    em_d, mask_d, state_d, _ = G.decode_slots(
        cfg, backend.params, state, cache, jax.random.PRNGKey(3), sparams,
        num_steps=steps,
    )

    # paged pool: the same prompt prefilled straight into its blocks
    pool = backend.init_paged_pool(2 * MB + 1, bs)
    # non-trivial physical placement: out-of-order block ids
    table = np.zeros((n_slots, MB), np.int32)
    row = np.asarray([5, 2, 7, 3], np.int32)
    table[1] = row
    state2, sparams2 = G.init_slots(n_slots, cfg.vocab_size)
    first2, pool, state2, sparams2 = _fill_slot_paged(
        backend, pool, state2, sparams2, 1, row, tokens, plen, key,
        sampling, jnp.int32(steps + 1), knobs,
    )
    em_p, mask_p, state_p, _, _ = backend.decode_slots_paged(
        state2, pool, jnp.asarray(table), jax.random.PRNGKey(3), sparams2,
        num_steps=steps,
    )

    assert int(first[0]) == int(first2[0])
    np.testing.assert_array_equal(np.asarray(mask_d), np.asarray(mask_p))
    np.testing.assert_array_equal(
        np.asarray(em_d)[np.asarray(mask_d)], np.asarray(em_p)[np.asarray(mask_p)]
    )


@pytest.mark.slow
def test_paged_engine_matches_dense_engine(solo_engine):
    """End-to-end: the same request mix through a paged fleet and a dense
    fleet produces identical greedy text."""
    dense = ContinuousEngine(
        solo_engine, n_slots=2, chunk_steps=4, slot_max_seq=96
    )
    try:
        want = [
            dense.submit(p, greedy=True, chat=False, max_tokens=12)
            for p in PROMPTS
        ]
    finally:
        dense.close()
    paged = ContinuousEngine(
        solo_engine, n_slots=2, chunk_steps=4, slot_max_seq=96,
        kv_pool_blocks=16, kv_block_size=16,
    )
    try:
        got = _submit_all(paged, PROMPTS, max_tokens=12)
        stats = paged.stats()
    finally:
        paged.close()
    for w, g in zip(want, got):
        assert w["status"] == g["status"] == "success"
        assert g["response"] == w["response"]
        assert g["tokens_generated"] == w["tokens_generated"]
    assert stats["paged"]["pool_blocks"] == 16
    # all blocks returned after completion
    assert stats["paged"]["free_blocks"] == 15


@pytest.mark.slow
def test_pool_backpressure_and_reuse(solo_engine):
    """A pool too small for all requests at once still serves every one:
    admission waits for released blocks (no failure, no deadlock), and
    freed blocks are reused across tenants with correct output."""
    # slot class 96 tokens -> 6 blocks/slot max; pool of 8 usable blocks
    # cannot hold two worst-case tenants at once
    cont = ContinuousEngine(
        solo_engine, n_slots=4, chunk_steps=4, slot_max_seq=96,
        kv_pool_blocks=9, kv_block_size=16,
    )
    try:
        solo = [
            solo_engine.generate(p, greedy=True, chat=False, max_tokens=40)
            for p in PROMPTS
        ]
        got = _submit_all(cont, PROMPTS, max_tokens=40)
        stats = cont.stats()
    finally:
        cont.close()
    for w, g in zip(solo, got):
        assert g["status"] == "success"
        assert g["response"] == w["response"]
    assert stats["paged"]["free_blocks"] == 8


@pytest.mark.slow
def test_request_exceeding_slot_class_rejected(solo_engine):
    cont = ContinuousEngine(
        solo_engine, n_slots=2, chunk_steps=4, slot_max_seq=64,
        kv_pool_blocks=16, kv_block_size=16,
    )
    try:
        out = cont.submit(
            " ".join(f"w{i}" for i in range(80)), greedy=True, chat=False,
            max_tokens=8,
        )
    finally:
        cont.close()
    assert out["status"] == "failed"
    assert out["error_type"] == "invalid_request"


@pytest.mark.slow
def test_paged_requires_capable_backend(solo_engine):
    with pytest.raises(ValueError, match="full slot-class"):
        ContinuousEngine(
            solo_engine, n_slots=2, chunk_steps=4, slot_max_seq=96,
            kv_pool_blocks=4, kv_block_size=16,  # < 6 blocks + trash
        )


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel (ops/paged_attention.py)


def _gather_attend(q, pool_k, pool_v, table, pos, window=None):
    """The hook's XLA gather path, stand-alone: the kernel's reference."""
    from distributed_llm_inference_tpu.ops.attention import (
        attend, slot_causal_mask,
    )

    B, _, H, Dh = q.shape
    KV, bs = pool_k.shape[1], pool_k.shape[2]
    MB = table.shape[1]
    gk = pool_k[table].transpose(0, 2, 1, 3, 4).reshape(B, KV, MB * bs, Dh)
    gv = pool_v[table].transpose(0, 2, 1, 3, 4).reshape(B, KV, MB * bs, Dh)
    mask = slot_causal_mask(pos, 1, MB * bs, window)
    return attend(q, gk, gv, mask)


def _walk_case(seed=5, quant=False):
    """A scattered pool, three slots' tables with trash-block tails, and
    one query per slot (GQA 8 / 2)."""
    from distributed_llm_inference_tpu.ops.kv_quant import (
        KVQuant, quantize_chunk,
    )

    B, H, KV, Dh, bs, MB, N = 4, 8, 2, 16, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), jnp.float32)
    pool_k = jax.random.normal(ks[1], (N, KV, bs, Dh), jnp.float32)
    pool_v = jax.random.normal(ks[2], (N, KV, bs, Dh), jnp.float32)
    if quant:
        qk, sk = quantize_chunk(pool_k.transpose(0, 2, 1, 3))
        qv, sv = quantize_chunk(pool_v.transpose(0, 2, 1, 3))
        pool_k = KVQuant(qk.transpose(0, 2, 1, 3), sk.transpose(0, 2, 1))
        pool_v = KVQuant(qv.transpose(0, 2, 1, 3), sv.transpose(0, 2, 1))
    table = jnp.asarray(
        [[5, 2, 7, 12], [1, 9, 0, 0], [11, 4, 6, 3], [0, 0, 0, 0]], jnp.int32
    )
    return q, pool_k, pool_v, table, bs, MB


# (positions, static window, window_dyn, softcap, int8 pool, active mask):
# every row of `table` but the last is a live slot; the last is a freed
# slot whose row is all trash blocks
_BS, _MB = 8, 4
WALK_CASES = {
    "pos-0": ([0, 0, 0, 0], None, None, None, False, None),
    "pos-block-minus-1": ([_BS - 1] * 4, None, None, None, False, None),
    "pos-block": ([_BS] * 4, None, None, None, False, None),
    "pos-table-end": ([_MB * _BS - 1, 2 * _BS - 1, _MB * _BS - 1, 0],
                      None, None, None, False, None),
    "pos-mixed": ([11, 7, 30, 3], None, None, None, False, None),
    "window-first-block-dead": ([29, 15, 31, 3], 9, None, None, False, None),
    "window-one-block": ([29, 15, 31, 3], 3, None, None, False, None),
    "window-dyn": ([29, 15, 31, 3], None, 9, None, False, None),
    "window-dyn-full": ([29, 15, 31, 3], None, -1, None, False, None),
    "softcap": ([11, 7, 30, 3], None, None, 5.0, False, None),
    "softcap-window": ([29, 15, 31, 3], 13, None, 9.0, False, None),
    "int8": ([11, 7, 30, 3], None, None, None, True, None),
    "int8-window": ([29, 15, 31, 3], 9, None, None, True, None),
    "inactive-slot": ([11, 7, 30, 3], None, None, None, False,
                      [True, False, True, True]),
    "freed-slot-trash-row": ([11, 7, 30, 31], None, None, None, False,
                             [True, True, True, False]),
    "freed-slot-trash-row-int8-window": ([11, 7, 30, 31], 9, None, None, True,
                                         [True, True, True, False]),
    "all-inactive": ([11, 7, 30, 3], None, None, None, False, [False] * 4),
}


@pytest.mark.parametrize(
    "case", sorted(WALK_CASES) + sorted(DECODE_BLOCK_CASES))
def test_paged_kernel_walk_matches_gather(case):
    """Kernel-level: the block walk == gather + attend on every live row;
    a row whose active flag is false is not walked (its output, which the
    caller discards, is zeros) and leaves the live rows' outputs as they
    are without the mask. WALK_CASES' shapes walk 4 pages a loop step (an
    int8 pool 1); the `blocks-` cases (tests/paged_walk_cases.py) walk 1,
    2, 4 and 8 by their shapes, with contexts that end at, past and short
    of a compute block."""
    if case in DECODE_BLOCK_CASES:
        return check_decode_block_case(case)
    from distributed_llm_inference_tpu.ops.attention import (
        attend, slot_causal_mask,
    )
    from distributed_llm_inference_tpu.ops.kv_quant import KVQuant, dequantize
    from distributed_llm_inference_tpu.ops.paged_attention import (
        paged_flash_attend,
    )

    pos, window, dyn, softcap, quant, active = WALK_CASES[case]
    q, pool_k, pool_v, table, bs, MB = _walk_case(quant=quant)
    assert (bs, MB) == (_BS, _MB)
    pos = jnp.asarray(pos, jnp.int32)
    got = np.asarray(paged_flash_attend(
        q, pool_k, pool_v, table, pos,
        None if dyn is None else jnp.int32(dyn),
        None if active is None else jnp.asarray(active),
        window=window, softcap=softcap, interpret=True,
    ))

    def view(leaf):
        if isinstance(leaf, KVQuant):
            leaf = dequantize(leaf)
        B, KV, Dh = table.shape[0], leaf.shape[1], leaf.shape[-1]
        return leaf[table].transpose(0, 2, 1, 3, 4).reshape(B, KV, MB * bs, Dh)

    w = window if dyn is None else (dyn if dyn > 0 else None)
    want = np.asarray(attend(
        q, view(pool_k), view(pool_v), slot_causal_mask(pos, 1, MB * bs, w),
        softcap=softcap,
    ))
    live = np.ones(len(pos), bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert np.all(got[~live] == 0.0)
    if active is not None:
        unmasked = np.asarray(paged_flash_attend(
            q, pool_k, pool_v, table, pos,
            None if dyn is None else jnp.int32(dyn),
            window=window, softcap=softcap, interpret=True,
        ))
        np.testing.assert_array_equal(got[live], unmasked[live])


@pytest.mark.parametrize("window", [None, 21])
@pytest.mark.slow
def test_paged_kernel_matches_gather(window):
    """Kernel-level: paged_flash_attend == gather+attend on a scattered
    out-of-order table, per-row positions, GQA grouping."""
    from distributed_llm_inference_tpu.ops.paged_attention import (
        paged_flash_attend,
    )

    B, H, KV, Dh, bs, MB, N = 3, 8, 2, 16, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), jnp.float32)
    pool_k = jax.random.normal(ks[1], (N, KV, bs, Dh), jnp.float32)
    pool_v = jax.random.normal(ks[2], (N, KV, bs, Dh), jnp.float32)
    # out-of-order physical placement, trash-block tails (block 0)
    table = jnp.asarray(
        [[5, 2, 7, 0], [1, 9, 0, 0], [11, 4, 6, 3]], jnp.int32
    )
    # rows mid-block, at a block edge, and at the last logical position
    pos = jnp.asarray([11, 7, MB * bs - 1], jnp.int32)
    got = paged_flash_attend(
        q, pool_k, pool_v, table, pos, window=window, interpret=True
    )
    want = _gather_attend(q, pool_k, pool_v, table, pos, window=window)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.slow
def test_paged_kernel_token_parity(solo_engine):
    """Engine-level: a paged decode with attn_impl='pallas' emits the
    exact token stream the XLA gather path emits (greedy, same params)."""
    eng_x = solo_engine
    cfg_p = eng_x.cfg.replace(attn_impl="pallas")
    eng_p = InferenceEngine(
        cfg_p, params=eng_x.backend.params,
        engine_cfg=EngineConfig(prefill_buckets=(32, 64)),
    )
    sampling = G.default_sampling(greedy=True)
    key = jax.random.PRNGKey(7)
    tokens = jnp.asarray(
        [[eng_x.cfg.bos_token_id, 21, 22, 23, 24, 25]], jnp.int32
    )
    tokens = jnp.pad(tokens, ((0, 0), (0, 26)),
                     constant_values=eng_x.cfg.pad_token_id)
    plen, n_slots, steps, bs, MB = jnp.int32(6), 2, 10, 8, 4
    knobs = (
        jnp.float32(1.0), jnp.int32(0), jnp.float32(1.0), True,
        jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(0.0), jnp.float32(0.0),
        jnp.zeros((eng_x.cfg.vocab_size,), bool),
    )
    table = np.zeros((n_slots, MB), np.int32)
    table[1] = np.asarray([3, 6, 2, 5], np.int32)
    streams = []
    for eng in (eng_x, eng_p):
        be = eng.backend
        state, sparams = G.init_slots(n_slots, eng.cfg.vocab_size)
        pool = be.init_paged_pool(2 * MB + 1, bs)
        _, pool, state, sparams = _fill_slot_paged(
            be, pool, state, sparams, 1, table[1], tokens, plen, key,
            sampling, jnp.int32(steps + 1), knobs,
        )
        em, mask, _, _, _ = be.decode_slots_paged(
            state, pool, jnp.asarray(table), jax.random.PRNGKey(3),
            sparams, num_steps=steps,
        )
        streams.append(np.asarray(em)[np.asarray(mask)])
    np.testing.assert_array_equal(streams[0], streams[1])


@pytest.mark.slow
def test_dense_fleet_under_pallas_serves_the_xla_text(solo_engine):
    """Engine-level: the dense continuous fleet under attn_impl='pallas'
    serves the exact greedy text the XLA fleet serves."""
    eng_x = solo_engine
    want = []
    cont = ContinuousEngine(eng_x, n_slots=2, chunk_steps=4, slot_max_seq=96)
    try:
        want = [
            cont.submit(p, greedy=True, chat=False, max_tokens=10)
            for p in PROMPTS
        ]
    finally:
        cont.close()
    eng_p = InferenceEngine(
        eng_x.cfg.replace(attn_impl="pallas"), params=eng_x.backend.params,
        engine_cfg=EngineConfig(prefill_buckets=(32, 64)),
    )
    cont_p = ContinuousEngine(eng_p, n_slots=2, chunk_steps=4, slot_max_seq=96)
    try:
        got = _submit_all(cont_p, PROMPTS, max_tokens=10)
    finally:
        cont_p.close()
    for w, g in zip(want, got):
        assert g["status"] == "success"
        assert g["response"] == w["response"]


# ---------------------------------------------------------------------------
# Paged KV on the pp mesh (round-3 review #2): the flagship memory feature
# on the reference's flagship topology.


@pytest.mark.slow
def test_pp_decode_slots_paged_matches_dense(eight_devices):
    """Device-level on pp=2: a slot decoding over the layer-sharded block
    pool emits the exact stream the pp dense fleet emits from the same
    prefill — gated ring writes redirect ungated scatters to the trash
    block without corrupting any live block."""
    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.runtime import create_backend

    cfg, backend = create_backend(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2)
    )
    sampling = G.default_sampling(greedy=True)
    key = jax.random.PRNGKey(7)
    tokens = jnp.asarray(
        [[cfg.bos_token_id, 11, 12, 13, 14, 15, 16, 17]], jnp.int32
    )
    tokens = jnp.pad(tokens, ((0, 0), (0, 24)), constant_values=cfg.pad_token_id)
    plen, n_slots, steps = jnp.int32(8), 4, 12
    bs, MB = 8, 4
    knobs = (
        jnp.float32(1.0), jnp.int32(0), jnp.float32(1.0), True,
        jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(0.0), jnp.float32(0.0),
        jnp.zeros((cfg.vocab_size,), bool),
    )

    assert backend.supports_paged

    # dense pp fleet
    scratch = backend.init_cache(1, MB * bs)
    first, _, scratch = backend.prefill(tokens, plen, scratch, key, sampling)
    state, sparams = G.init_slots(n_slots, cfg.vocab_size)
    cache = backend.init_cache(n_slots, MB * bs)
    cache, state, sparams = G.insert_slot(
        cfg, cache, scratch, state, sparams, 1, first[0], plen,
        jnp.int32(steps + 1), *knobs,
    )
    em_d, mask_d, _, _ = backend.decode_slots(
        state, cache, jax.random.PRNGKey(3), sparams, num_steps=steps
    )

    # paged pp pool: the same prompt prefilled into out-of-order blocks
    pool = backend.init_paged_pool(2 * MB + 1, bs)
    table = np.zeros((n_slots, MB), np.int32)
    row = np.asarray([5, 2, 7, 3], np.int32)
    table[1] = row
    state2, sparams2 = G.init_slots(n_slots, cfg.vocab_size)
    first2, pool, state2, sparams2 = _fill_slot_paged(
        backend, pool, state2, sparams2, 1, row, tokens, plen, key,
        sampling, jnp.int32(steps + 1), knobs,
    )
    em_p, mask_p, _, _, _ = backend.decode_slots_paged(
        state2, pool, jnp.asarray(table), jax.random.PRNGKey(3), sparams2,
        num_steps=steps,
    )

    assert int(first[0]) == int(first2[0])
    np.testing.assert_array_equal(np.asarray(mask_d), np.asarray(mask_p))
    np.testing.assert_array_equal(
        np.asarray(em_d)[np.asarray(mask_d)],
        np.asarray(em_p)[np.asarray(mask_p)],
    )


@pytest.mark.slow
def test_pp_paged_engine_matches_dense_engine(eight_devices):
    """End-to-end on pp=2: the same request mix through a paged continuous
    fleet and a dense one on the pipeline mesh produces identical greedy
    text, and the pool returns every block afterwards."""
    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.runtime import create_engine

    eng = create_engine(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(prefill_buckets=(32, 64)),
    )
    dense = ContinuousEngine(eng, n_slots=2, chunk_steps=4, slot_max_seq=96)
    try:
        want = [
            dense.submit(p, greedy=True, chat=False, max_tokens=12)
            for p in PROMPTS
        ]
    finally:
        dense.close()
    paged = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, slot_max_seq=96,
        kv_pool_blocks=16, kv_block_size=16,
    )
    try:
        got = _submit_all(paged, PROMPTS, max_tokens=12)
        stats = paged.stats()
    finally:
        paged.close()
    for w, g in zip(want, got):
        assert w["status"] == g["status"] == "success"
        assert g["response"] == w["response"]
    assert stats["paged"]["free_blocks"] == 15


@pytest.mark.slow
def test_pp_paged_uneven_layer_split(eight_devices):
    """pp=3 over 4 layers (uneven: padded layer slots) with an int8 pool:
    paged + kv_quant + pp + layer padding all compose — identical greedy
    text to the dense int8 pp fleet."""
    from distributed_llm_inference_tpu import MeshConfig, get_model_config
    from distributed_llm_inference_tpu.runtime import create_engine

    cfg = get_model_config("test-llama-tiny", kv_quant="int8")
    eng = create_engine(
        cfg, mesh_cfg=MeshConfig(pp=3),
        engine_cfg=EngineConfig(prefill_buckets=(32,)),
    )
    dense = ContinuousEngine(eng, n_slots=2, chunk_steps=4, slot_max_seq=64)
    try:
        want = [
            dense.submit(p, greedy=True, chat=False, max_tokens=8)
            for p in PROMPTS[:2]
        ]
    finally:
        dense.close()
    paged = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, slot_max_seq=64,
        kv_pool_blocks=12, kv_block_size=16,
    )
    try:
        got = _submit_all(paged, PROMPTS[:2], max_tokens=8)
    finally:
        paged.close()
    for w, g in zip(want, got):
        assert w["status"] == g["status"] == "success"
        assert g["response"] == w["response"]


@pytest.mark.parametrize("window", [None, 21])
@pytest.mark.slow
def test_paged_kernel_dequantizes_int8_pool(window):
    """Kernel-level: paged_flash_attend over KVQuant pool leaves == the
    gather path over the dequantized pool — the table walk streams int8
    and dequantizes per block in the prologue."""
    from distributed_llm_inference_tpu.ops.kv_quant import (
        KVQuant, dequantize, quantize_chunk,
    )
    from distributed_llm_inference_tpu.ops.paged_attention import (
        paged_flash_attend,
    )

    B, H, KV, Dh, bs, MB, N = 3, 8, 2, 16, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), jnp.float32)
    raw_k = jax.random.normal(ks[1], (N, KV, bs, Dh), jnp.float32)
    raw_v = jax.random.normal(ks[2], (N, KV, bs, Dh), jnp.float32)
    # quantize_chunk scales over the trailing Dh axis given [..., T, KV, Dh];
    # pool layout is [N, KV, bs, Dh] -> per-(block, head, slot) scales
    qk, sk = quantize_chunk(raw_k.transpose(0, 2, 1, 3))
    qv, sv = quantize_chunk(raw_v.transpose(0, 2, 1, 3))
    pk = KVQuant(qk.transpose(0, 2, 1, 3), sk.transpose(0, 2, 1))
    pv = KVQuant(qv.transpose(0, 2, 1, 3), sv.transpose(0, 2, 1))
    table = jnp.asarray(
        [[5, 2, 7, 0], [1, 9, 0, 0], [11, 4, 6, 3]], jnp.int32
    )
    pos = jnp.asarray([11, 7, MB * bs - 1], jnp.int32)
    got = paged_flash_attend(
        q, pk, pv, table, pos, window=window, interpret=True
    )
    want = _gather_attend(
        q, dequantize(pk), dequantize(pv), table, pos, window=window
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.slow
def test_paged_int8_pallas_token_parity(solo_engine):
    """Engine-level: an int8 paged fleet under attn_impl='pallas' (the
    dequantizing table-walk kernel) emits the exact token stream the int8
    gather path emits."""
    base = solo_engine.cfg.replace(kv_quant="int8")
    streams = []
    for impl in ("xla", "pallas"):
        eng = InferenceEngine(
            base.replace(attn_impl=impl), params=solo_engine.backend.params,
            engine_cfg=EngineConfig(prefill_buckets=(32, 64)),
        )
        cont = ContinuousEngine(
            eng, n_slots=2, chunk_steps=4, slot_max_seq=96,
            kv_pool_blocks=16, kv_block_size=16,
        )
        try:
            streams.append([
                cont.submit(p, greedy=True, chat=False, max_tokens=10)["response"]
                for p in PROMPTS
            ])
        finally:
            cont.close()
    assert streams[0] == streams[1]


@pytest.mark.slow
def test_paged_kernel_softcap_scale_window_dyn():
    """Round-5: the paged kernel covers score-scale overrides, Gemma-2
    softcapping, and a traced per-layer window (window_dyn) — each must
    match the gather + attend reference, and the dynamic-window spelling
    must match the static one."""
    from distributed_llm_inference_tpu.ops.attention import (
        attend, slot_causal_mask,
    )
    from distributed_llm_inference_tpu.ops.paged_attention import (
        paged_flash_attend,
    )

    B, H, KV, Dh, bs, MB, N = 3, 8, 2, 16, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), jnp.float32)
    pool_k = jax.random.normal(ks[1], (N, KV, bs, Dh), jnp.float32)
    pool_v = jax.random.normal(ks[2], (N, KV, bs, Dh), jnp.float32)
    table = jnp.asarray(
        [[5, 2, 7, 0], [1, 9, 0, 0], [11, 4, 6, 3]], jnp.int32
    )
    pos = jnp.asarray([11, 7, MB * bs - 1], jnp.int32)

    def gather_ref(window, scale, softcap):
        gk = pool_k[table].transpose(0, 2, 1, 3, 4).reshape(B, KV, MB * bs, Dh)
        gv = pool_v[table].transpose(0, 2, 1, 3, 4).reshape(B, KV, MB * bs, Dh)
        mask = slot_causal_mask(pos, 1, MB * bs, window)
        return attend(q, gk, gv, mask, scale=scale, softcap=softcap)

    for W, sc, cap in [(13, 0.3, None), (None, 0.25, 5.0), (13, None, 9.0)]:
        want = np.asarray(gather_ref(W, sc, cap))
        got = np.asarray(paged_flash_attend(
            q, pool_k, pool_v, table, pos, window=W, scale=sc, softcap=cap,
            interpret=True,
        ))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=str((W, sc, cap)))
        got_dyn = np.asarray(paged_flash_attend(
            q, pool_k, pool_v, table, pos, jnp.int32(W if W else -1),
            scale=sc, softcap=cap, interpret=True,
        ))
        np.testing.assert_allclose(got_dyn, want, rtol=2e-5, atol=2e-5,
                                   err_msg=str((W, sc, cap)))


@pytest.mark.slow
def test_paged_pallas_gemma2_fleet_parity():
    """Engine-level: a gemma-2-style model (softcap + query scaling +
    per-layer 'even' windows) through a paged fleet under
    attn_impl='pallas' emits exactly the XLA gather fleet's greedy text —
    the per-layer width rides the kernel's window_dyn operand."""
    cfg_x = get_model_config("test-gemma2-tiny", eos_token_id=-1).replace(
        attn_window=8
    )
    params = InferenceEngine(
        cfg_x, engine_cfg=EngineConfig(prefill_buckets=(32,))
    ).backend.params

    def run(cfg):
        eng = InferenceEngine(
            cfg, params=params, engine_cfg=EngineConfig(prefill_buckets=(32,))
        )
        cont = ContinuousEngine(
            eng, n_slots=2, chunk_steps=4, slot_max_seq=96,
            kv_pool_blocks=16, kv_block_size=16,
        )
        try:
            return [
                cont.submit(p, greedy=True, chat=False, max_tokens=10)
                for p in PROMPTS[:2]
            ]
        finally:
            cont.close()

    want = run(cfg_x)
    got = run(cfg_x.replace(attn_impl="pallas"))
    for w, g in zip(want, got):
        assert w["status"] == g["status"] == "success", (w, g)
        assert g["response"] == w["response"]


# ---------------------------------------------------------------------------
# The paged hooks' contract (ISSUE 29): the STACKED pool and a layer index.
# forward_layers carries the pool through its layer scan and hands each
# layer's hook the whole leaves; the hook (or, where it can, the kernel
# itself) writes the step's tokens into that layer and no other.

_HOOK_KINDS = {  # leaf kind -> (model preset, overrides)
    "raw": ("test-llama-tiny", {}),  # head dim 16: XLA scatters into a slice
    "wide": ("test-llama-tiny", {"head_dim_override": 128}),  # the kernel writes
    "int8": ("test-llama-tiny", {"kv_quant": "int8"}),
    "latent": ("test-mla-moe-tiny", {}),
}
_HOOK_L, _HOOK_N, _HOOK_BS, _HOOK_MB = 3, 10, 16, 3


def _hook_case(kind, impl):
    """(cfg, a random stacked pool of _HOOK_L layers as numpy leaves, the
    pool as the hooks take it)."""
    from distributed_llm_inference_tpu.config import resolve_attn_impl
    from distributed_llm_inference_tpu.ops.kv_quant import KVQuant

    preset, kw = _HOOK_KINDS[kind]
    cfg = resolve_attn_impl(
        get_model_config(preset, dtype="float32", **kw), impl)
    pool = P.init_pool(cfg, _HOOK_N, _HOOK_BS, n_layers=None if kind == "latent" else _HOOK_L)
    pool.pop("routed", None)
    rng = np.random.default_rng(7)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    pool = jax.tree.map(fill, pool)
    return cfg, pool


def _hook_operands(cfg, kind, rows, rng):
    """(q, k, v) of `rows` single-token batch rows in the hook's shapes."""
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    if kind == "latent":
        R = cfg.latent_row
        return f32(rows, 1, cfg.n_heads, R), f32(rows, 1, 1, R), None
    Dh = cfg.head_dim
    return (f32(rows, 1, cfg.n_heads, Dh), f32(rows, 1, cfg.n_kv_heads, Dh),
            f32(rows, 1, cfg.n_kv_heads, Dh))


def _leaf_arrays(leaf):
    """A pool leaf's arrays as numpy (an int8 leaf is data and scales)."""
    return [np.asarray(a) for a in jax.tree.leaves(leaf)]


def _expected_token(kind, x):
    """What one token x [rows, 1, KV, Dh] leaves in each array of a leaf."""
    from distributed_llm_inference_tpu.ops.kv_quant import quantize_chunk

    if kind == "int8":
        return [np.asarray(a)[:, 0] for a in quantize_chunk(x)]
    return [np.asarray(x)[:, 0]]


def _check_hook_writes(kind, before, after, layer, k, v, blk, off, live):
    """The layer holds the live rows' tokens at (blk, :, off); every other
    layer is untouched bit for bit, and so is everything else in the layer
    outside the trash block and the rows' own (block, offset) cells."""
    for name, x in (("k", k), ("v", v)):
        if x is None:
            continue
        key = name if name in before else None
        leaves = ([(s, before[s], after[s]) for s in before] if key is None
                  else [(key, before[key], after[key])])
        for stack, b_leaf, a_leaf in leaves:
            for b, a, want in zip(_leaf_arrays(b_leaf), _leaf_arrays(a_leaf),
                                  _expected_token(kind, x)):
                others = [i for i in range(b.shape[0]) if i != layer]
                if key is None and stack != "moe":  # the other latent stack
                    np.testing.assert_array_equal(a, b)
                    continue
                np.testing.assert_array_equal(a[others], b[others])
                allowed = np.zeros(b.shape[1:], bool)
                allowed[P.TRASH_BLOCK] = True
                for r in range(len(blk)):
                    allowed[blk[r], :, off[r]] = True
                    if live[r]:
                        np.testing.assert_array_equal(
                            a[layer, blk[r], :, off[r]], want[r])
                np.testing.assert_array_equal(
                    np.where(allowed, 0, a[layer]), np.where(allowed, 0, b[layer]))


def _call_hook(hook, cfg, kind, q, k, v, pool, pos, mask, gate, layer):
    """The hook as forward_layers' scan calls it; the latent family hands
    it the "moe" stack's leaf. Returns (attn, the pool after)."""
    if kind == "latent":
        attn, new, _ = hook(cfg, q, k, None, pool["moe"], None, pos, mask,
                            gate, None, None, jnp.int32(layer))
        return attn, {**pool, "moe": new}
    attn, nk, nv = hook(cfg, q, k, v, pool["k"], pool["v"], pos, mask, gate,
                        None, None, jnp.int32(layer))
    return attn, {"k": nk, "v": nv}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", sorted(_HOOK_KINDS))
def test_paged_hook_writes_its_layer_of_the_stacked_pool(kind, impl):
    """Decode rows at a block's last position, a block's first and the
    middle of one, and a freed slot; the first layer and the last; and a
    gated-off write (the pp ring's), which may touch the trash block only."""
    from distributed_llm_inference_tpu.ops.attention import slot_causal_mask

    cfg, pool = _hook_case(kind, impl)
    bs, MB = _HOOK_BS, _HOOK_MB
    table = jnp.asarray([[3, 7, 2], [5, 1, 0], [4, 6, 8], [9, 0, 0]], jnp.int32)
    pos = jnp.asarray([bs - 1, bs, 2 * bs + 5, 3], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    q, k, v = _hook_operands(cfg, kind, 4, np.random.default_rng(1))
    mask = slot_causal_mask(pos, 1, MB * bs)
    blk = np.asarray(table)[np.arange(4), np.asarray(pos) // bs]
    off = np.asarray(pos) % bs
    hook = P.make_paged_hook(table, active)
    assert hook.paged
    L = pool["moe" if kind == "latent" else "k"].shape[0]
    attn = {}
    for layer in (0, L - 1):
        attn[layer], after = _call_hook(hook, cfg, kind, q, k, v, pool, pos,
                                        mask, None, layer)
        _check_hook_writes(kind, pool, after, layer, k, v, blk, off,
                           np.asarray(active))
    if kind != "latent":  # "a latent cache has no gated write (no pp)"
        _, after = _call_hook(hook, cfg, kind, q, k, v, pool, pos, mask,
                              jnp.asarray(False), 0)
        _check_hook_writes(kind, pool, after, 0, k, v,
                           np.zeros(4, int), off, np.zeros(4, bool))
    if impl == "pallas":  # the two attention paths agree on the live rows
        other, _ = _hook_case(kind, "xla")
        want, _ = _call_hook(P.make_paged_hook(table, active), other, kind, q,
                             k, v, pool, pos, mask, None, L - 1)
        np.testing.assert_allclose(np.asarray(attn[L - 1])[:3],
                                   np.asarray(want)[:3], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", sorted(_HOOK_KINDS))
def test_ragged_fill_hook_writes_its_layer_of_the_stacked_pool(kind, impl):
    """A prefill chunk that straddles a block edge, a decode row at a
    block's first position and launch padding, on the flat token axis."""
    cfg, pool = _hook_case(kind, impl)
    bs = _HOOK_BS
    table = jnp.asarray([[3, 7, 2], [5, 1, 0]], jnp.int32)
    entries = [(0, bs - 5, 13, P.RAGGED_PREFILL), (1, bs, 1, P.RAGGED_DECODE)]
    meta, tok_row, tok_pos, _, _ = P.build_ragged_meta(entries, width=32, tile=8)
    q, k, v = _hook_operands(cfg, kind, 32, np.random.default_rng(2))
    live = tok_row >= 0
    blk = np.where(live, np.asarray(table)[np.maximum(tok_row, 0), tok_pos // bs],
                   P.TRASH_BLOCK)
    off = tok_pos % bs
    hook = P.make_ragged_fill_hook(table, jnp.asarray(meta), jnp.asarray(tok_row))
    assert hook.paged
    pos = jnp.asarray(tok_pos)
    L = pool["moe" if kind == "latent" else "k"].shape[0]
    attn = {}
    for layer in (0, L - 1):
        attn[layer], after = _call_hook(hook, cfg, kind, q, k, v, pool, pos,
                                        None, None, layer)
        _check_hook_writes(kind, pool, after, layer, k, v, blk, off, live)
    if kind != "latent":
        _, after = _call_hook(hook, cfg, kind, q, k, v, pool, pos, None,
                              jnp.asarray(False), 0)
        _check_hook_writes(kind, pool, after, 0, k, v, np.zeros(32, int),
                           off, np.zeros(32, bool))
    if impl == "pallas":
        other, _ = _hook_case(kind, "xla")
        want, _ = _call_hook(hook, other, kind, q, k, v, pool, pos, None, None,
                             L - 1)
        np.testing.assert_allclose(np.asarray(attn[L - 1])[live],
                                   np.asarray(want)[live], atol=1e-4, rtol=1e-4)
