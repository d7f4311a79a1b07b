"""Ragged paged attention (ops/paged_attention.ragged_paged_attend +
engine/paged ragged ingest) tests.

The bar: the ragged path is a LAUNCH strategy, not a semantics change —
mixed prefill+decode rows of arbitrary length in one kernel launch must
match the dense reference bit-for-fp32-tolerance (incl. int8 kv_quant and
sliding windows), the engine's ragged admission must be greedy-identical
to the solo engine on the same weights, and the block-prefix planner must
reuse at EXACT chunk depth where a bucketed plan would degrade to a bucket
boundary.
Every kernel here runs under interpret=True on CPU (tests/conftest.py
pins DLI_PALLAS_INTERPRET=1 — the tier-1 bit-exactness switch).
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.ops.attention import attend
from distributed_llm_inference_tpu.ops.flash_attention import (
    resolve_interpret,
)
from distributed_llm_inference_tpu.ops.kv_quant import KVQuant, dequantize
from distributed_llm_inference_tpu.ops.paged_attention import (
    RAGGED_DECODE,
    RAGGED_PREFILL,
    ragged_paged_attend,
)

from paged_walk_cases import RAGGED_BLOCK_CASES, check_ragged_block_case


# -- kernel-level bit-exactness (ragged vs dense reference) -------------------

def _mixed_case(seed=0, quant=False):
    """A pool + tables + mixed metadata: two prefill rows of different
    lengths (one mid-sequence, one from zero) and two decode rows."""
    rng = np.random.default_rng(seed)
    N, KV, bs, Dh, H, MB = 12, 2, 8, 16, 4, 4
    shape = (N, KV, bs, Dh)
    if quant:
        pool_k = KVQuant(
            jnp.asarray(rng.integers(-127, 127, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]), jnp.float32),
        )
        pool_v = KVQuant(
            jnp.asarray(rng.integers(-127, 127, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]), jnp.float32),
        )
    else:
        pool_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = jnp.asarray(
        [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 1], [2, 5, 9, 3]],
        jnp.int32,
    )
    entries = [
        (0, 5, 13, RAGGED_PREFILL),  # mid-sequence chunk (ctx 0..17)
        (1, 20, 1, RAGGED_DECODE),  # decode at pos 20
        (2, 0, 6, RAGGED_PREFILL),  # cold chunk from position 0
        (3, 9, 1, RAGGED_DECODE),  # decode at pos 9
    ]
    W, tile = 32, 4
    meta, tok_row, tok_pos, offs, stats = P.build_ragged_meta(
        entries, width=W, tile=tile
    )
    q = jnp.asarray(rng.normal(size=(W, H, Dh)), jnp.float32)
    return (pool_k, pool_v, table, entries, meta, tok_row, tok_pos, offs,
            stats, q, bs, MB, KV, Dh)


def _dense_ref(pool_k, pool_v, table, row, q_rows, positions, bs, MB,
               window=None):
    """Per-row reference: gather the row's logical view, run the stock
    masked attention at the given absolute positions."""
    def view(leaf):
        g = dequantize(KVQuant(leaf.q[table[row]], leaf.s[table[row]])) \
            if isinstance(leaf, KVQuant) else leaf[table[row]]
        KV, Dh = g.shape[1], g.shape[-1]
        return g.transpose(1, 0, 2, 3).reshape(1, KV, MB * bs, Dh)

    kv_pos = np.arange(MB * bs)
    mask = jnp.asarray(kv_pos[None, :] <= np.asarray(positions)[:, None])
    if window is not None:
        mask &= jnp.asarray(
            kv_pos[None, :] > np.asarray(positions)[:, None] - window
        )
    return attend(q_rows[None], view(pool_k), view(pool_v), mask[None])[0]


@pytest.mark.parametrize("quant", [False, True])
def test_ragged_kernel_matches_dense_reference(quant):
    (pool_k, pool_v, table, entries, meta, tok_row, tok_pos, offs, stats,
     q, bs, MB, KV, Dh) = _mixed_case(quant=quant)
    out = ragged_paged_attend(
        q, pool_k, pool_v, table, jnp.asarray(meta), interpret=True
    )
    for (row, start, length, _), off in zip(entries, offs):
        ref = _dense_ref(
            pool_k, pool_v, table, row, q[off : off + length],
            np.arange(start, start + length), bs, MB,
        )
        np.testing.assert_allclose(
            np.asarray(out[off : off + length]), np.asarray(ref),
            rtol=2e-5, atol=2e-5,
        )


# Hand-built tile plans over _mixed_case's pool (bs 8, MB 4, tile 4):
# (row, q_start, q_len) per tile, q_len 0 = a tile that holds nothing.
# Pad tiles sit between and after live ones, with their predecessor's
# placement (as build_ragged_meta writes them) or with a row of their own.
RAGGED_WALK_CASES = {
    "pads-between-and-after": (
        [(0, 5, 4), (0, 9, 0), (1, 20, 1), (1, 20, 0), (3, 9, 1), (3, 9, 0),
         (3, 9, 0), (2, 0, 4)], None, None, False),
    "pads-first": (
        [(0, 0, 0), (0, 0, 0), (2, 0, 4), (2, 4, 2), (1, 31, 1), (3, 7, 1),
         (3, 8, 1), (0, 0, 1)], None, None, False),
    "only-pads": ([(0, 5, 0)] * 8, None, None, False),
    "block-edges": (
        [(0, 0, 1), (1, 7, 1), (2, 8, 1), (3, 31, 1), (0, 4, 4), (1, 28, 4),
         (2, 6, 4), (3, 0, 0)], None, None, False),
    "window-first-block-dead": (
        [(0, 14, 4), (1, 20, 1), (1, 20, 0), (3, 31, 1), (2, 28, 4),
         (2, 28, 0), (0, 9, 1), (0, 9, 0)], 7, None, False),
    "window-dyn": (
        [(0, 14, 4), (1, 20, 1), (1, 20, 0), (3, 31, 1), (2, 28, 4),
         (2, 28, 0), (0, 9, 1), (0, 9, 0)], None, 7, False),
    "softcap": (
        [(0, 5, 4), (0, 9, 0), (1, 20, 1), (1, 20, 0), (3, 9, 1), (3, 9, 0),
         (3, 9, 0), (2, 0, 4)], None, None, False, 5.0),
    "int8-pads-between": (
        [(0, 5, 4), (0, 9, 0), (1, 20, 1), (1, 20, 0), (3, 9, 1), (3, 9, 0),
         (3, 9, 0), (2, 0, 4)], None, None, True),
    "int8-window": (
        [(0, 14, 4), (1, 20, 1), (1, 20, 0), (3, 31, 1), (2, 28, 4),
         (2, 28, 0), (0, 9, 1), (0, 9, 0)], 7, None, True),
}


@pytest.mark.parametrize(
    "case", sorted(RAGGED_WALK_CASES) + sorted(RAGGED_BLOCK_CASES))
def test_ragged_kernel_walk_matches_dense_reference(case):
    """Every live query == the dense reference at its position; a tile
    that holds nothing is not walked and writes zeros; queries past a
    tile's q_len are padding (fully masked: zeros). RAGGED_WALK_CASES'
    shapes walk 4 pages a loop step (an int8 pool 1); the `blocks-` cases
    (tests/paged_walk_cases.py) walk 1, 2, 4 and 8 by their shapes and
    write their tiles' rows into a stacked pool as the step programs do."""
    if case in RAGGED_BLOCK_CASES:
        return check_ragged_block_case(case)
    tiles, window, dyn, quant, *rest = RAGGED_WALK_CASES[case]
    softcap = rest[0] if rest else None
    (pool_k, pool_v, table, _, _, _, _, _, _, q, bs, MB, KV, Dh) = \
        _mixed_case(seed=3, quant=quant)
    tile = 4
    assert q.shape[0] == len(tiles) * tile
    meta = np.asarray(
        [(r, s, n, RAGGED_DECODE if n == 1 else RAGGED_PREFILL)
         for r, s, n in tiles], np.int32,
    )
    out = np.asarray(ragged_paged_attend(
        q, pool_k, pool_v, table, jnp.asarray(meta),
        None if dyn is None else jnp.int32(dyn),
        window=window, softcap=softcap, interpret=True,
    ))
    w = window if dyn is None else dyn

    def view(leaf, row):
        g = dequantize(KVQuant(leaf.q[table[row]], leaf.s[table[row]])) \
            if isinstance(leaf, KVQuant) else leaf[table[row]]
        return g.transpose(1, 0, 2, 3).reshape(1, KV, MB * bs, Dh)

    for g, (row, start, n) in enumerate(tiles):
        got = out[g * tile : (g + 1) * tile]
        assert np.all(got[n:] == 0.0), (case, g)
        if n == 0:
            continue
        positions = np.arange(start, start + n)
        kv_pos = np.arange(MB * bs)
        mask = kv_pos[None, :] <= positions[:, None]
        if w is not None:
            mask &= kv_pos[None, :] > positions[:, None] - w
        ref = attend(
            q[g * tile : g * tile + n][None], view(pool_k, row),
            view(pool_v, row), jnp.asarray(mask)[None], softcap=softcap,
        )[0]
        np.testing.assert_allclose(got[:n], np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"{case} {g}")


def test_ragged_kernel_sliding_window():
    (pool_k, pool_v, table, entries, meta, tok_row, tok_pos, offs, stats,
     q, bs, MB, KV, Dh) = _mixed_case()
    win = 7
    out = ragged_paged_attend(
        q, pool_k, pool_v, table, jnp.asarray(meta), window=win,
        interpret=True,
    )
    # traced per-layer width (window_dyn) must agree with the static one
    out_dyn = ragged_paged_attend(
        q, pool_k, pool_v, table, jnp.asarray(meta), jnp.int32(win),
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_dyn), rtol=1e-6, atol=1e-6
    )
    for (row, start, length, _), off in zip(entries, offs):
        ref = _dense_ref(
            pool_k, pool_v, table, row, q[off : off + length],
            np.arange(start, start + length), bs, MB, window=win,
        )
        np.testing.assert_allclose(
            np.asarray(out[off : off + length]), np.asarray(ref),
            rtol=2e-5, atol=2e-5,
        )


def test_ragged_meta_builder():
    meta, tok_row, tok_pos, offs, stats = P.build_ragged_meta(
        [(0, 5, 13, P.RAGGED_PREFILL), (1, 20, 1, P.RAGGED_DECODE)],
        width=24, tile=4,
    )
    # entry 0: 13 tokens -> 4 tiles (3 full + 1 of length 1); entry 1
    # starts on the next tile boundary
    assert offs == [0, 16]
    assert list(meta[:, 2]) == [4, 4, 4, 1, 1, 0]
    assert stats == {
        "tiles": 6, "pad_tiles": 1, "prefill_rows": 1, "decode_rows": 1,
    }
    # pad tile inherits its predecessor's placement (DMA repetition) with
    # q_len 0; padding tokens carry row -1 (scattered to the trash block)
    assert meta[5, 0] == meta[4, 0] and meta[5, 1] == meta[4, 1]
    assert tok_row[13] == -1 and tok_row[12] == 0 and tok_row[16] == 1
    assert tok_pos[16] == 20
    with pytest.raises(ValueError):
        P.build_ragged_meta(
            [(0, 0, 25, P.RAGGED_PREFILL)], width=24, tile=4
        )
    with pytest.raises(ValueError):
        P.build_ragged_meta([(0, 0, 1, 0)], width=10, tile=4)


def test_interpret_env_switch():
    """tests/conftest.py pins DLI_PALLAS_INTERPRET=1, and the shared
    resolver honors it — the tier-1 contract that every Pallas kernel
    here actually ran its own math, not a silent XLA fallback."""
    assert os.environ.get("DLI_PALLAS_INTERPRET") == "1"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    old = os.environ["DLI_PALLAS_INTERPRET"]
    try:
        os.environ["DLI_PALLAS_INTERPRET"] = "0"
        # explicit 0: the backend default decides only via TPU presence
        assert resolve_interpret(None) is False
    finally:
        os.environ["DLI_PALLAS_INTERPRET"] = old


# -- engine-level: ragged admission vs the solo engine --------------------------

PREFIX_CFG = dict(dtype="float32", eos_token_id=-1, max_seq_len=256)


@pytest.fixture(scope="module", params=["test-llama-tiny", "test-gpt2-tiny"])
def family_setup(request):
    cfg = get_model_config(request.param, **PREFIX_CFG)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _cont(cfg, params, attn_impl=None, **ecfg):
    """(the paged fleet, its solo engine: the greedy reference on the same
    weights)."""
    if attn_impl is not None:
        cfg = cfg.replace(attn_impl=attn_impl)
    eng = InferenceEngine(
        cfg, params=params,
        engine_cfg=EngineConfig(
            prefix_cache_entries=4, prefill_buckets=(64, 128, 256), **ecfg,
        ),
    )
    return eng, ContinuousEngine(
        eng, n_slots=4, chunk_steps=8, slot_max_seq=256,
        kv_pool_blocks=48, kv_block_size=16,
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


def test_ragged_greedy_identical_to_solo(family_setup):
    """Mixed fleet (concurrent prompts of different lengths, warm prefix
    reuse) — the ragged path must be token-identical to the solo engine's
    bucketed prefill on a dense cache, both families."""
    cfg, params = family_setup
    shared = " ".join(f"ctx{j}" for j in range(16))
    prompts = [
        "the quick brown fox jumps over the lazy dog",
        shared + " question one",
        shared + " question two",
        "short",
    ]
    eng, cont = _cont(cfg, params)
    try:
        want = [
            eng.generate(p, max_tokens=10, greedy=True, chat=False)["response"]
            for p in prompts
        ]
        # serial first pass warms the prefix chains; the threaded wave
        # exercises a mixed fleet on the warm path
        warm = [
            cont.submit(p, max_tokens=10, greedy=True, chat=False)
            for p in prompts
        ]
        wave = _submit_all(cont, prompts, max_tokens=10)
        st = cont.stats()
    finally:
        cont.close()
    assert all(r["status"] == "success" for r in warm + wave), (warm, wave)
    assert st["paged"]["ragged_width"] == 64
    assert any(r.get("prefix_cached_tokens") for r in wave)
    assert [r["response"] for r in warm] == want
    assert [r["response"] for r in wave] == want


def test_ragged_kernel_path_greedy_identical(family_setup):
    """attn_impl='pallas' routes the ragged ingest through the Pallas
    kernel (interpret mode on CPU); greedy output must match the XLA
    gather twin — the kernel-vs-fallback bit-exactness gate at the
    serving level."""
    cfg, params = family_setup
    if cfg.arch == "gpt2":
        pytest.skip("attn_impl is a llama-family config knob")
    prompts = ["a b c d e f", "the quick brown fox jumps"]
    outs = {}
    for impl in ("xla", "pallas"):
        _, cont = _cont(cfg, params, attn_impl=impl)
        try:
            outs[impl] = [
                cont.submit(p, max_tokens=8, greedy=True, chat=False)[
                    "response"
                ]
                for p in prompts
            ]
        finally:
            cont.close()
    assert outs["pallas"] == outs["xla"]


def test_ragged_int8_pool_greedy_identical(family_setup):
    """int8 kv_quant composes with the ragged path: quantize-on-scatter
    into the pool must serve the same greedy stream as the solo engine
    (which quantizes into its dense cache)."""
    cfg, params = family_setup
    if cfg.arch == "gpt2":
        pytest.skip("kv_quant is a llama-family config knob")
    qcfg = cfg.replace(kv_quant="int8")
    prompts = ["the quick brown fox", "hello world"]
    eng, cont = _cont(qcfg, params)
    try:
        want = [
            eng.generate(p, max_tokens=8, greedy=True, chat=False)["response"]
            for p in prompts
        ]
        got = [
            cont.submit(p, max_tokens=8, greedy=True, chat=False)["response"]
            for p in prompts
        ]
    finally:
        cont.close()
    assert got == want


def test_exact_depth_reuse_no_bucket_degradation():
    """The planner regression the ragged path exists to fix: a hit whose
    tail no prefill bucket fits would degrade the reuse depth under a
    bucketed plan, but reuses at EXACT chunk depth on the ragged path —
    mark() accounting matches the planned depth, and the hit serves the
    solo engine's greedy text."""
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", eos_token_id=-1,
        max_seq_len=128,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(1))

    eng = InferenceEngine(
        cfg, params=params,
        engine_cfg=EngineConfig(
            prefix_cache_entries=4, prefill_buckets=(64,),
        ),
    )
    cont = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, slot_max_seq=128,
        kv_pool_blocks=24, kv_block_size=16,
    )
    try:
        # 96-token shared head (6 full blocks), ~100-token prompts: the
        # 4-token tail would need the 64 bucket, and 96 + 64 > 128, so a
        # bucketed plan would degrade the depth to 64
        base = "x" * 96
        want = eng.generate(base + "wxyz", max_tokens=4, greedy=True,
                            chat=False)
        r1 = cont.submit(base + "abcd", max_tokens=4, greedy=True,
                         chat=False)
        r2 = cont.submit(base + "wxyz", max_tokens=4, greedy=True,
                         chat=False)
        st = cont.stats()["prefix_cache"]
    finally:
        cont.close()
    assert r1["status"] == "success" and r2["status"] == "success"
    # exact chunk depth: 6 blocks of 16
    assert r2.get("prefix_cached_tokens", 0) == 96
    # mark() accounting follows the PLANNED depth
    assert st["dedup_saved_tokens"] == 96
    assert r2["response"] == want["response"]


def test_ragged_single_program_any_tail():
    """One compiled (extend, prefill) program pair serves every tail:
    admissions with different prompt lengths must not add backend
    launches beyond ceil(tail/width), and tails <= width are exactly ONE
    launch (the single-launch contract the analysis ragged rule pins on
    the artifact)."""
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", eos_token_id=-1,
        max_seq_len=256,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        cfg, params=params,
        # chunked_prefill=False: this test pins the PER-ADMISSION ragged
        # ingest launches (extend/prefill pair); the chunked scheduler's
        # mixed-launch counting lives in tests/test_scheduler.py
        engine_cfg=EngineConfig(
            prefix_cache_entries=0, chunked_prefill=False,
        ),
    )
    cont = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, slot_max_seq=256,
        kv_pool_blocks=40, kv_block_size=16,
    )
    calls = {"extend": 0, "prefill": 0}
    be = cont.backend
    orig_extend, orig_prefill = be.extend_ragged_paged, be.prefill_ragged_paged

    def count_extend(*a, **k):
        calls["extend"] += 1
        return orig_extend(*a, **k)

    def count_prefill(*a, **k):
        calls["prefill"] += 1
        return orig_prefill(*a, **k)

    be.extend_ragged_paged = count_extend
    be.prefill_ragged_paged = count_prefill
    try:
        # 30-token tail (< width 64): one prefill launch, zero extends
        cont.submit("a" * 30, max_tokens=3, greedy=True, chat=False)
        assert calls == {"extend": 0, "prefill": 1}
        # 150-token tail: two whole-width extends + one prefill
        cont.submit("b" * 150, max_tokens=3, greedy=True, chat=False)
        assert calls == {"extend": 2, "prefill": 2}
        # a third, different tail length must not recompile the programs
        n_prog = be.ragged_program_count()
        cont.submit("c" * 45, max_tokens=3, greedy=True, chat=False)
        assert be.ragged_program_count() == n_prog
    finally:
        be.extend_ragged_paged = orig_extend
        be.prefill_ragged_paged = orig_prefill
        cont.close()


def test_ragged_metrics_and_pool_hygiene():
    """dli_ragged_* families populate (rows by kind, tile liveness, the
    compiled-program gauge) and the pool frees fully after the fleet
    drains — the ragged scatter leaks no blocks."""
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", eos_token_id=-1,
        max_seq_len=256,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        cfg, params=params,
        # per-admission ingest metrics (phase=extend/prefill launches);
        # the chunked scheduler's phase=mixed accounting is covered in
        # tests/test_scheduler.py
        engine_cfg=EngineConfig(
            prefix_cache_entries=0, chunked_prefill=False,
        ),
    )
    cont = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, slot_max_seq=256,
        kv_pool_blocks=40, kv_block_size=16,
    )
    try:
        for p in ("hello world", "x" * 100):
            r = cont.submit(p, max_tokens=4, greedy=True, chat=False)
            assert r["status"] == "success"
        snap = eng.metrics.snapshot()

        def series(name):
            return {
                tuple(sorted(s["labels"].items())): s["value"]
                for s in snap.get(name, {}).get("series", [])
            }

        rows = series("dli_ragged_rows_total")
        assert rows.get((("kind", "prefill"),), 0) >= 2
        tiles = series("dli_ragged_tiles_total")
        assert tiles.get((("state", "live"),), 0) > 0
        assert tiles.get((("state", "pad"),), 0) > 0
        launches = series("dli_ragged_launches_total")
        assert launches.get((("phase", "prefill"),), 0) == 2
        progs = series("dli_ragged_compiled_programs")
        assert progs.get((), 0) >= 1
    finally:
        cont.close()
    assert cont._alloc.free_blocks == cont._alloc.n_blocks - 1
    assert cont._alloc.outstanding == 0
