"""A decode chunk ends when its last live row does (ISSUE 46;
engine/paged.steps_while_active, engine/continuous._launch_chunk / _fetch).

Below the engine: `decode_slots_paged` against the static scan it replaced
(written out here as the parent had it), for an autoregressive dense model,
an autoregressive routed one and a block-diffusion one. At the engine: what
the launch record forecasts (`steps_live`), what the fetch reads back
(`steps_run`) and what dli_decode_chunk_steps_total counts, for a row that
ends by its budget and for one that ends by a stop token the position model
cannot see. The XLA attention path throughout: no interpreted kernel.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M

K = 16  # steps a chunk is dispatched with
BS = 16  # pool block size


# -- the program against the static scan ---------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def static_scan(cfg, params, state, pool, table, key, sparams, diff):
    """The chunk as a jax.lax.scan of K steps, whatever the rows have left
    (the parent's decode_slots_paged), with each step's `token` row beside
    what it emitted."""
    pool = P._routed_reset(pool)
    if cfg.diffusion_block:
        def forward(carry, sub):
            state, diff, pool = carry
            logits, pool = P._forward_blocks_paged(cfg, params, state, diff, pool, table)
            state, diff, emit, ok = P.diffusion_step(cfg, state, sparams, diff, logits, sub)
            return (state, diff, pool), (emit.T, ok.T, state.token)

        (state, diff, pool), (emitted, mask, tokens) = jax.lax.scan(
            forward, (state, diff, pool), jax.random.split(key, K))
        rows = (K * cfg.diffusion_block, -1)
        return emitted.reshape(rows), mask.reshape(rows), state, pool, diff, tokens

    def body(carry, sub):
        state, pool = carry
        logits, pool = P._forward_step_paged(
            cfg, params, state.token[:, None], pool, table, state.pos, active=state.active)
        new, emit, can_emit = G.slot_step(cfg, state, sparams, logits, sub)
        return (new, pool), (emit, can_emit, new.token)

    (state, pool), (emitted, mask, tokens) = jax.lax.scan(
        body, (state, pool), jax.random.split(key, K))
    return emitted, mask, state, pool, None, tokens


def _fleet(cfg, remaining):
    """Three slots that start decoding at position 0 over an empty pool
    (the budgets in tokens), the middle one never let."""
    S = len(remaining)
    pool = P.init_pool(cfg, 1 + 4 * S, BS)
    table = jnp.asarray([[1 + 4 * b + j for j in range(4)] for b in range(S)], jnp.int32)
    state, sparams = G.init_slots(S, cfg.vocab_size)
    left = jnp.asarray(remaining, jnp.int32)
    state = state._replace(
        token=jnp.asarray([5 + 7 * b for b in range(S)], jnp.int32),
        active=left > 0, remaining=left)
    diff = P.init_diffusion(cfg, S) if cfg.diffusion_block else None
    return pool, table, state, sparams, diff


def _written(leaf, table, pos):
    """A pool leaf [L, N, KV, bs, D] as each row's positions below pos[b]."""
    rows = []
    for b, blocks in enumerate(np.asarray(table)):
        own = np.moveaxis(leaf[:, blocks], 1, 2)  # [L, KV, MB, bs, D]
        rows.append(own.reshape(*own.shape[:2], -1, own.shape[-1])[:, :, :pos[b]])
    return np.concatenate(rows, axis=2)


@pytest.mark.parametrize("name", ["test-llama-tiny", "test-mla-moe-tiny", "test-sdar-tiny"])
def test_the_chunk_equals_the_static_scan_on_every_step_that_runs(name):
    cfg = get_model_config(name, dtype="float32", eos_token_id=-1, attn_impl="xla")
    # (one program, not a compile a leaf: what the weights are does not matter)
    params = jax.jit(functools.partial(M.init_params, cfg))(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(11)
    Bd = max(1, cfg.diffusion_block)
    # (budgets, the step at which the last live row ends): a block-diffusion
    # row reveals its whole block a forward here, so a budget of n tokens is
    # ceil(n / block) forwards; 0 = a fleet that was dead at dispatch
    for remaining, last in (([3, 0, 5 * Bd], 5), ([2, 0, 40 * Bd], K), ([0, 0, 0], 0)):
        pool, table, state, sparams, diff = _fleet(cfg, remaining)
        want = static_scan(cfg, params, state, pool, table, key, sparams, diff)
        pool, *_ = _fleet(cfg, remaining)  # (the chunk's pool is donated)
        got = P.decode_slots_paged(
            cfg, params, state, pool, table, key, sparams, num_steps=K, diff=diff)
        steps_run = int(got[-1])
        assert steps_run == last, (remaining, steps_run)
        # what the rows emitted, and pad / False from the exit on (the scan's
        # dead steps emit pad / False too: the whole arrays are equal)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        assert not np.asarray(got[1])[steps_run * Bd:].any()
        assert (np.asarray(got[0])[steps_run * Bd:] == cfg.pad_token_id).all()
        assert np.asarray(got[1]).sum() == sum(min(r, K * Bd) for r in remaining)
        for field in ("pos", "remaining", "active"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got[2], field)), np.asarray(getattr(want[2], field)), field)
        # `token` as the last step that RAN left it (the scan's dead steps go
        # on to write pad over an ended row's last token)
        np.testing.assert_array_equal(
            np.asarray(got[2].token),
            np.asarray(want[5][steps_run - 1] if steps_run else state.token))
        # the pool at every position a live step wrote (a dead step writes
        # garbage at its row's own frozen position, which nothing attends),
        # and what the expert layers routed: a dead step routes nothing
        pos = np.asarray(got[2].pos)
        for leaf in got[3]:
            a, b = np.asarray(got[3][leaf]), np.asarray(want[3][leaf])
            if leaf != "routed":
                a, b = _written(a, table, pos), _written(b, table, pos)
            np.testing.assert_array_equal(a, b, leaf)
        if Bd > 1:
            for field in ("open", "skip", "owed", "owe"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got[4], field)), np.asarray(getattr(want[4], field)), field)


# -- the engine's accounting of it ----------------------------------------------

SLOTS, LAG = 2, 2
PROMPT = "a lone request"


@functools.cache
def _weights():
    cfg = get_model_config("test-llama-tiny", dtype="float32", max_seq_len=256)
    return M.init_params(cfg, jax.random.PRNGKey(0))


def _cont(**cfg_kw):
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", max_seq_len=256, attn_impl="xla", **cfg_kw)
    eng = InferenceEngine(
        cfg, params=_weights(),
        engine_cfg=EngineConfig(prefix_cache_entries=0, chunked_prefill=True,
                                step_token_budget=64, prefill_buckets=(64, 128)))
    cont = ContinuousEngine(
        eng, n_slots=SLOTS, chunk_steps=K, chunk_lag=LAG, slot_max_seq=128,
        kv_pool_blocks=32, kv_block_size=BS, restart_backoff_s=0.01)
    cont.records = []  # every launch record, as its fetch closed it
    fetch = cont._fetch

    def spy(packed_dev, t_launch, rec):
        out = fetch(packed_dev, t_launch, rec)
        cont.records.append(dict(rec))
        return out

    gen_text = cont._gen_text

    def ids(req):
        out = gen_text(req)
        cont.ids = list(out[0])  # the last answer's token ids
        return out

    cont._fetch, cont._gen_text = spy, ids
    return cont


def _ask(cont, n):
    """One request alone in the fleet: (its token ids, its chunks' records,
    the counter's run / cut over them)."""
    def steps():
        fam = cont.engine.metrics.snapshot()["dli_decode_chunk_steps_total"]["series"]
        return {s["labels"]["state"]: s["value"] for s in fam}

    before, n_recs = steps(), len(cont.records)
    res = cont.submit(PROMPT, max_tokens=n, greedy=True, chat=False)
    assert res["status"] == "success", res
    # the chunks the lag dispatched behind the answer's end are fetched once
    # the worker finds nothing else to do
    for _ in range(500):
        if cont._steps_inflight == 0:
            break
        time.sleep(0.01)
    assert cont._steps_inflight == 0
    after = steps()
    chunks = [r for r in cont.records[n_recs:] if r["phase"] == "chunk"]
    assert res["tokens_generated"] == len(cont.ids)
    return cont.ids, chunks, {s: after.get(s, 0) - before.get(s, 0) for s in ("run", "cut")}


@pytest.fixture(scope="module")
def fleet():
    cont = _cont(eos_token_id=-1)
    yield cont
    cont.close()


def test_a_lone_rows_last_chunk_runs_its_live_steps_alone(fleet):
    # 33 tokens: 32 decode steps are two whole chunks, nothing to cut
    whole, chunks, steps = _ask(fleet, 33)
    assert [(r["steps"], r["steps_live"], r["steps_run"]) for r in chunks] == [(K, K, K)] * 2
    assert steps == {"run": 2 * K, "cut": 0}
    # 22 tokens: N - 1 = 21 = 16 + 5
    ids, chunks, steps = _ask(fleet, 22)
    assert len(ids) == 22 and ids == whole[:22]  # the text it returned before
    assert [(r["steps"], r["steps_live"], r["steps_run"]) for r in chunks] == [
        (K, K, K), (K, 5, 5)]
    assert steps == {"run": K + 5, "cut": K - 5}
    assert steps["run"] + steps["cut"] == sum(r["steps"] for r in chunks)


def test_a_stop_token_ends_the_chunk_where_the_model_forecast_all_of_it(fleet):
    ids = _ask(fleet, 48)[0]
    # a token of the second chunk that the answer had not held before: decode
    # step s (from 0) chooses token s + 1 of the answer
    at = next(i for i in range(K + 2, 2 * K) if ids[i] not in ids[:i])
    j = (at - 1) % K  # the stop token is chosen at the chunk's step j
    cont = _cont(eos_token_id=int(ids[at]))
    try:
        got, chunks, steps = _ask(cont, 48)
    finally:
        cont.close()
    assert got == ids[:at]  # break before append
    # the chunk in which the row died ran to the step that chose the stop
    # token; the model, which cannot see it, forecast a whole chunk. Behind it
    # the lag had dispatched further chunks for a fleet that was already
    # dead: they run nothing
    assert [(r["steps_live"], r["steps_run"]) for r in chunks[:2]] == [(K, K), (K, j + 1)]
    assert len(chunks) > 2 and all(r["steps_run"] == 0 for r in chunks[2:])
    assert steps == {"run": K + j + 1, "cut": K * len(chunks) - (K + j + 1)}
