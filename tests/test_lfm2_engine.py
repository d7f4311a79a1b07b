"""The lfm2 family through the served engine (ISSUE 34): `engine/continuous.py`
over the paged pool at `test-lfm2-tiny`, seeded random weights. Every token
the fleet delivers is held against the plain reference's LOGITS (its margin
below the reference's best, in logit-sigmas), never against tokens alone:
chunked prefill beside decode rows, slots let again, a prefix hit after the
first tenant has gone and after an unrelated chain's eviction, interpreted
Pallas and XLA attention, float32 and bfloat16; the counters and launch-record
fields the benchmark reads; and what the family cannot take, refused at
start-up with a message.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, MeshConfig, create_engine
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine

from lfm2_util import ref_logits

SEED, BS = 3, 16


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
    def __init__(self, impl="xla", dtype="float32", budget=16, slots=2, pool=24,
                 chunk=4, **kw):
        self.dtype = dtype
        self.eng = create_engine(
            "test-lfm2-tiny", seed=SEED, attn_impl=impl, dtype=dtype,
            engine_cfg=EngineConfig(prefix_cache_entries=8, step_token_budget=budget))
        self.eng.tokenizer = WordTok()
        self.ce = ContinuousEngine(
            self.eng, n_slots=slots, chunk_steps=chunk, kv_pool_blocks=pool,
            kv_block_size=BS, kv_shadow=False, slot_max_seq=160, **kw)
        self.cfg = self.eng.cfg
        self.records = []
        record = self.ce._launch_record
        self.ce._launch_record = lambda *a, **k: self.records.append(record(*a, **k)) \
            or self.records[-1]

    def ask_all(self, asks):
        """asks: [(ids, max_tokens)] sent together; the envelopes, with `ids`
        (the generated ids) added."""
        out = [None] * len(asks)

        def one(i, ids, mt):
            out[i] = self.ce.submit(words(ids), max_tokens=mt, greedy=True, chat=False)

        ts = [threading.Thread(target=one, args=(i, *a)) for i, a in enumerate(asks)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(300)
        for r in out:
            assert r is not None and r.get("status") == "success", r
            r["ids"] = WordTok().encode(r["response"]) if r["response"] else []
        return out

    def margins(self, prompt, res):
        seq = prompt + res["ids"]
        lg = ref_logits(self.cfg, SEED, seq, jnp.dtype(self.dtype))
        lg = lg[len(prompt) - 1:len(seq) - 1]
        chosen = np.asarray(res["ids"])
        return (lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen]) / lg.std()

    def series(self, name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in self.eng.metrics.snapshot().get(name, {}).get("series", [])}


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


# six rows for two slots: prompts of one chunk and of several (45 and 70
# tokens at a 16-token step budget), so chunks share launches with decode
# rows and every slot is let again, most of them while its previous
# tenant's last launch is in flight (engine/continuous._release_ended)
ASKS = [(20, 14), (21, 9), (33, 12), (5, 10), (45, 8), (70, 6)]


def _served(f, tol):
    asks = [(prompt_ids(n), mt) for n, mt in ASKS]
    res = f.ask_all(asks)
    worst = []
    for (ids, mt), r in zip(asks, res):
        assert r["prompt_tokens"] == len(ids) and 0 < len(r["ids"]) <= mt
        worst.append(f.margins(ids, r))
    m = np.concatenate(worst)
    assert m.max() <= tol, (m.max(), (m > 0).mean())
    return m


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_served_tokens_are_the_references_choice(impl):
    f = fleet(impl=impl)
    _served(f, 1e-4)
    # every slot was let again to a tenant that started from zeros, and the
    # position model released every row that its budget ended
    assert f.series("dli_conv_state_resets_total")[()] >= len(ASKS)
    assert f.series("dli_conv_tail_writes_total")[()] >= sum(
        (n + mt - 1) // BS for n, mt in ASKS) - len(ASKS)
    assert f.series("dli_slot_release_total").get((("by", "model"),), 0) > 0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_served_in_bfloat16_stays_near_the_references_choice(impl):
    """bfloat16 against the float32 reference on the same (bfloat16) weights:
    a rounding flips a near-tie (a router's, a token's) now and then; a wrong
    or missing state reads over 1e-2 in float32 (tests/test_lfm2.py's
    mutants) and moves whole rows here."""
    m = _served(fleet(impl=impl, dtype="bfloat16"), 4.0)
    assert m.mean() < 0.3 and (m > 0).mean() < 0.5, (m.mean(), (m > 0).mean())


def test_a_prefix_hit_is_exact_after_the_first_tenant_has_gone_and_after_an_eviction():
    f = fleet(impl="xla", pool=20, slots=2)
    doc = prompt_ids(70, salt=4)
    first = f.ask_all([(doc + [11, 12, 13], 8)])[0]
    assert not first.get("prefix_cached_tokens")
    before = f.series("dli_prefix_state_tokens_total")
    # the first tenant has gone: its blocks live on in the index alone
    again = f.ask_all([(doc + [21, 22, 23, 24], 10)])[0]
    assert again["prefix_cached_tokens"] == 64  # four blocks of 16
    assert f.margins(doc + [21, 22, 23, 24], again).max() <= 1e-4
    after = f.series("dli_prefix_state_tokens_total")
    assert after[()] - before.get((), 0) == 64
    # an unrelated chain comes and is evicted (19 blocks: the pool must evict
    # to admit two 100-token rows beside the document's chain) ...
    other = [prompt_ids(100, salt=s) for s in (5, 6)]
    f.ask_all([(o, 4) for o in other])
    f.ask_all([(prompt_ids(100, salt=7), 4)])
    assert f.ce._bpx.evictions > 0
    # ... and whatever of the document's chain is still mapped restores exactly
    third = f.ask_all([(doc + [31, 32], 9)])[0]
    assert f.margins(doc + [31, 32], third).max() <= 1e-4
    fresh = Fleet(impl="xla", pool=20, slots=2)
    try:
        cold = fresh.ask_all([(doc + [31, 32], 9)])[0]
    finally:
        fresh.ce.close()
    assert cold["ids"] == third["ids"] and not cold.get("prefix_cached_tokens")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_derived_width_serves_the_tokens_a_16_token_budget_serves(impl):
    """The launch width is derived from what the model streams (a routed
    bank of four experts to one computed: 512 flat tokens) and is a LAUNCH
    shape: the same greedy tokens as at step_token_budget=16, each the
    reference's choice, through a prefix hit's restored tail (RAGGED_FIRST at
    64) that rides a launch beside a decoding row, over several blocks'
    tails written by ONE chunk; and the two series count what the launch
    records say."""
    doc, row = prompt_ids(70, salt=9), prompt_ids(20, salt=9)
    got = {}
    for budget in (16, None):
        f = Fleet(impl=impl, budget=budget)
        try:
            cold = f.ask_all([(doc + [11, 12, 13], 8)])[0]
            beside, n0 = [], len(f.records)
            t = threading.Thread(
                target=lambda: beside.extend(f.ask_all([(row, 120)])))
            t.start()
            deadline = time.time() + 120
            while True:  # the row's own prefill, then a launch that decodes it
                new = f.records[n0:]
                armed = [i for i, r in enumerate(new) if r["prefill_chunks"]]
                if armed and any(r["decode_rows"] for r in new[armed[0] + 1:]):
                    break
                assert time.time() < deadline
                time.sleep(0.002)
            hit = f.ask_all([(doc + [21, 22, 23, 24], 10)])[0]
            t.join(300)
            width = f.ce.stats()["scheduler"]["step_width"]
            tiles = f.series("dli_ragged_tiles_total")
            gauge = f.series("dli_sched_step_width_tokens")
        finally:
            f.ce.close()
        assert hit["prefix_cached_tokens"] == 64 and not cold.get("prefix_cached_tokens")
        for ids, r in ((doc + [11, 12, 13], cold), (doc + [21, 22, 23, 24], hit),
                       (row, beside[0])):
            assert f.margins(ids, r).max() <= 1e-4
        got[budget] = [cold["ids"], hit["ids"], beside[0]["ids"]]
        mixed = [r for r in f.records if r["phase"] == "mixed"]
        assert width == (512 if budget is None else 24)  # 16: two rows + a tile
        assert gauge == {(): width}
        assert all(r["tiles"] == width // 8 for r in mixed)
        restored = [r for r in mixed if r["state_restored_tokens"]]
        assert [r["state_restored_tokens"] for r in restored] == [64]
        assert restored[0]["decode_rows"] == 1  # beside the decoding row
        # the record's tiles are the ones dli_ragged_tiles_total counts
        assert tiles[(("state", "live"),)] + tiles[(("state", "pad"),)] == sum(
            r["tiles"] for r in mixed)
        assert tiles[(("state", "live"),)] == sum(r["tiles_live"] for r in mixed)
        if budget is None:  # each prompt landed in one launch, four tails in one chunk
            assert [r["prefill_tokens"] for r in mixed if r["prefill_chunks"]] == [73, 20, 10]
            assert mixed[0]["conv_tail_writes"] == 4
    assert got[None] == got[16]


def test_the_launch_record_carries_the_states_fields():
    f = fleet(impl="xla")
    f.records.clear()
    doc = prompt_ids(40, salt=8)
    f.ask_all([(doc, 6)])
    f.ask_all([(doc + [9, 9, 9], 20)])
    mixed = [r for r in f.records if r["phase"] == "mixed"]
    assert all({"conv_tail_writes", "state_restored_tokens", "conv_state_resets"} <= set(r)
               for r in f.records)
    assert sum(r["conv_state_resets"] for r in mixed) == 1  # the cold ask
    assert sum(r["state_restored_tokens"] for r in mixed) == 32  # the hit: two blocks
    # 40 + 6 tokens fill two blocks; the hit's 11-token tail and 20 answers a third
    assert sum(r["conv_tail_writes"] for r in f.records) == 2 + 1


def test_the_solo_engines_contracts_are_refused_with_a_message():
    f = fleet(impl="xla")
    for kw in ({"seed": 3}, {"logprobs": True}, {"speculative": True}, {"num_beams": 2}):
        r = f.ce.submit(words(prompt_ids(9)), max_tokens=4, greedy=True, chat=False, **kw)
        assert r["status"] == "failed" and "recurrent state" in r["error"], (kw, r)
    r = f.eng.generate(words(prompt_ids(9)), max_tokens=4, greedy=True, chat=False)
    assert r["status"] == "failed" and "continuous engine" in r["error"]


def _engine(**kw):
    eng = create_engine("test-lfm2-tiny", seed=SEED, **kw)
    eng.tokenizer = WordTok()
    return eng


@pytest.mark.parametrize("what,make", [
    ("quant", lambda: _engine(quant="int8")),
    ("kv_quant", lambda: _engine(kv_quant="int8")),
    ("mesh", lambda: _engine(mesh_cfg=MeshConfig(pp=2))),
    ("dense fleet", lambda: ContinuousEngine(_engine(), n_slots=2)),
    ("shadow", lambda: ContinuousEngine(
        _engine(engine_cfg=EngineConfig(prefix_cache_entries=8)), n_slots=2,
        kv_pool_blocks=16, kv_block_size=BS, kv_shadow=True, slot_max_seq=64)),
    ("unchunked", lambda: ContinuousEngine(
        _engine(engine_cfg=EngineConfig(chunked_prefill=False)), n_slots=2,
        kv_pool_blocks=16, kv_block_size=BS, kv_shadow=False, slot_max_seq=64)),
    ("speculative", lambda: ContinuousEngine(
        _engine(engine_cfg=EngineConfig(spec_decode=True)), n_slots=2,
        kv_pool_blocks=16, kv_block_size=BS, kv_shadow=False, slot_max_seq=64)),
])
def test_what_the_family_cannot_take_is_refused_at_start_up(what, make):
    with pytest.raises(ValueError, match="recurrent layers") as e:
        make()
    assert "test-lfm2-tiny" in str(e.value), what
