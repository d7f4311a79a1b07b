"""The minicpm_sala family through the served engine (ISSUE 48):
`engine/continuous.py` over the paged pool at `test-sala-tiny`, seeded
random weights. What the fleet delivers is held against the plain
reference's LOGITS (cellbench/reference/sparse_linear_hybrid.py: each token's
margin below the reference's best, in logit-sigmas): chunked prefill beside
decode rows, below and above the tiny dense length, through pool, compressed
keys and matrix state; a prefix hit restored from a state snapshot against a
cold run; hits cut to the deepest snapshot and shortened by an evicted one;
a slot let again; the launch record's counts against a hand count; and what
the family cannot take, refused at start-up with a message.
"""

import os
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, MeshConfig, create_engine
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "cellbench"))

from harness import manifest  # noqa: E402

REF = manifest.load_module("reference", "sparse_linear_hybrid")
REF.Q_BLOCK = 16  # (the tiny sequences are a few blocks of queries)
SEED, BS = 3, 8
CONFIG = manifest.load_json(
    os.path.join(ROOT, "tests", "data", "sala", "configs", "tiny-sala.json"))


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


_PARAMS = {}


def ref_logits(seq, dtype="float32"):
    if dtype not in _PARAMS:
        _PARAMS[dtype] = REF.make_params(CONFIG, SEED, jnp.dtype(dtype))
    x = REF.forward(CONFIG, _PARAMS[dtype], seq)
    return np.asarray(REF.logits(CONFIG, _PARAMS[dtype], x[:len(seq)]))


class Fleet:
    def __init__(self, impl="xla", dtype="float32", budget=24, slots=2, pool=64,
                 chunk=4, snapshots=6, **kw):
        self.eng = create_engine(
            "test-sala-tiny", seed=SEED, attn_impl=impl, dtype=dtype,
            engine_cfg=EngineConfig(prefix_cache_entries=8, step_token_budget=budget,
                                    state_snapshots=snapshots))
        self.eng.tokenizer = WordTok()
        self.ce = ContinuousEngine(
            self.eng, n_slots=slots, chunk_steps=chunk, kv_pool_blocks=pool,
            kv_block_size=BS, kv_shadow=False, slot_max_seq=160, **kw)
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
            t.join(300)
        for r in out:
            assert r is not None and r.get("status") == "success", r
            r["ids"] = WordTok().encode(r["response"]) if r["response"] else []
        return out

    def ask(self, ids, mt):
        return self.ask_all([(ids, mt)])[0]


def margins(prompt, gen, dtype="float32"):
    lg = ref_logits(prompt + gen, dtype)[len(prompt) - 1:len(prompt) + len(gen) - 1]
    return (lg.max(-1) - lg[np.arange(len(gen)), gen]) / lg.std()


@pytest.fixture(scope="module", params=["xla", "pallas"])
def fleet(request):
    return Fleet(impl=request.param)


def test_prefill_then_decode_through_the_pool_is_the_references_forward(fleet):
    """Two rows at once: one ends below the tiny dense length (24), one
    prefills 70 tokens in chunks of 24 flat tokens beside the other's
    decode rows and decodes past it: every delivered token is the
    reference's top-1 at a margin of rounding (float32)."""
    short, long = prompt_ids(9, 1), prompt_ids(70, 2)
    a, b = fleet.ask_all([(short, 10), (long, 12)])
    for prompt, res in ((short, a), (long, b)):
        assert len(res["ids"]) >= 8
        np.testing.assert_allclose(margins(prompt, res["ids"]), 0.0, atol=1e-4)
    mixed = [r for r in fleet.records if r["phase"] == "mixed"]
    assert any(r["sparse_rows"] for r in fleet.records)
    assert any(r["sparse_rows"] < r["state_rows"] for r in fleet.records)
    assert all(r["kv_tokens"] <= r["kv_tokens_visible"] for r in fleet.records)
    assert any(r["kv_tokens"] < r["kv_tokens_visible"] for r in mixed)


def test_a_hit_restored_from_a_snapshot_is_a_cold_run_exactly(fleet):
    """A second prompt shares 66 tokens of the first's 70: the hit is 64
    deep (the first left snapshots at 56 and 64), starts its row's states
    from the snapshot, and delivers what a fleet that never saw the first
    prompt delivers, token for token; against the reference at rounding."""
    base = prompt_ids(70, 3)
    again = base[:66] + prompt_ids(9, 4)
    fleet.ask(base, 6)
    held = fleet.ce._bpx.snap_stats()["held"]
    hit = fleet.ask(again, 10)
    assert hit.get("prefix_cached_tokens") == 64 and held >= 2
    cold = Fleet(impl=fleet.eng.cfg.attn_impl).ask(again, 10)
    assert cold.get("prefix_cached_tokens", 0) == 0
    assert hit["ids"] == cold["ids"]
    np.testing.assert_allclose(margins(again, hit["ids"]), 0.0, atol=1e-4)


def test_a_hit_is_cut_to_the_deepest_snapshot_and_shortened_by_an_evicted_one():
    """The index holds a 70-token prompt's 8 blocks and snapshots at 56 and
    64. A prompt that shares only 60 tokens matches 7 blocks and is cut to
    56, the deepest that has a snapshot (and leaves one of its own at 72, the
    pool's third). One that shares 50 matches 6 blocks and finds no
    snapshot: cold; its own two (at 48, a block of the first prompt's chain,
    and at 56, its own) take the least recently used ones' places: the first
    prompt's at 64, then at 56. A prompt that shares 66 tokens now hits 48
    deep, where a snapshot is left, and is right all the same."""
    f = Fleet(snapshots=3)
    base = prompt_ids(70, 5)
    f.ask(base, 4)
    assert f.ask(base[:60] + prompt_ids(12, 6), 4)["prefix_cached_tokens"] == 56
    assert f.ask(base[:50] + prompt_ids(12, 7), 4).get("prefix_cached_tokens", 0) == 0
    assert f.ce._bpx.snap_stats() == {"held": 3, "free": 0, "pool": 3}
    events = {e: f.ce._bpx._m_snaps.labels(event=e).value
              for e in ("taken", "restored", "evicted")}
    assert events == {"taken": 5, "restored": 1, "evicted": 2}
    last = base[:66] + prompt_ids(9, 8)
    res = f.ask(last, 6)
    assert res["prefix_cached_tokens"] == 48
    np.testing.assert_allclose(margins(last, res["ids"]), 0.0, atol=1e-4)


def test_a_slot_let_again_starts_from_zeros():
    """One slot, two tenants in turn: the second's states start from zeros,
    not from what the first left (its tokens are the reference's), and the
    reset is counted."""
    f = Fleet(slots=1)
    first, second = prompt_ids(40, 9), prompt_ids(33, 10)
    f.ask(first, 6)
    res = f.ask(second, 8)
    np.testing.assert_allclose(margins(second, res["ids"]), 0.0, atol=1e-4)
    assert f.ce._m_lin_resets.value == 2


def test_the_launch_records_counts_are_the_hand_count():
    """One 70-token prompt alone, 24 flat tokens a step (one decode tile is
    reserved): chunks of 16 tokens, cut at 56 and 64 where the snapshots are
    due; positions visible and read by hand: below 24 all of them, past it
    top-4 blocks of 8 with the last as far as it is filled."""
    f = Fleet()
    prompt = prompt_ids(70, 11)
    f.ask(prompt, 5)
    mixed = [r for r in f.records if r["phase"] == "mixed" and r["prefill_tokens"]]
    ends = np.cumsum([r["prefill_tokens"] for r in mixed]).tolist()
    assert ends[-1] == 70 and 56 in ends and 64 in ends
    assert sum(r["state_snapshots_taken"] for r in mixed) == 2
    for r, n in zip(mixed, ends):
        assert r["kv_tokens_visible"] == n and r["state_rows"] == 1
        assert r["kv_tokens"] == (n if n < 24 else min(n, 3 * 8 + (n - 1) % 8 + 1))
        assert r["sparse_rows"] == int(n >= 24)
        assert r["ck_scored"] == -(-n // 2)  # a key every 2 tokens
    assert mixed[0]["conv_state_resets"] == 1  # (the field of every such fleet)
    chunk = [r for r in f.records if r["phase"] == "chunk"]
    steps = sum(r["state_rows"] for r in chunk)
    assert steps == sum(r["sparse_rows"] for r in chunk) and steps >= 1
    # the answer's positions 71 .. 74 visible, a step each: 36 + 36 + 37 + 37
    assert sum(r["ck_scored"] for r in chunk) == 146
    assert f.ce._m_ck_scored.value == sum(r["ck_scored"] for r in f.records)
    assert "dli_sparse_scored_keys_total" in f.eng.metrics.render()


def test_the_state_row_counter_is_the_launch_records():
    """`dli_linear_state_rows_total` over two requests side by side: touched
    = the launch records' `state_rows` (a row-step that carried a token: the
    states the scan moves), held = slots x the launches' steps (the leaf a
    pass over it would move); both series are on /metrics."""
    f = Fleet()
    f.ask_all([(prompt_ids(40, 3), 6), (prompt_ids(20, 5), 9)])
    touched, held = (f.ce._m_lin_rows.labels(state=s).value
                     for s in ("touched", "held"))
    assert touched == sum(r["state_rows"] for r in f.records) > 0
    assert held == 2 * sum(r["steps"] for r in f.records)
    assert any(r["phase"] == "chunk" and r["steps"] > 1 for r in f.records)
    assert touched < held
    text = f.eng.metrics.render()
    for s in ("touched", "held"):
        assert f'dli_linear_state_rows_total{{state="{s}"}}' in text


def test_start_up_refuses_what_the_family_does_not_carry():
    eng = create_engine("test-sala-tiny", seed=SEED)
    with pytest.raises(ValueError, match="no dense fleet"):
        ContinuousEngine(eng, n_slots=2)
    cached = create_engine("test-sala-tiny", seed=SEED,
                           engine_cfg=EngineConfig(prefix_cache_entries=8))
    with pytest.raises(ValueError, match="shadow store"):
        ContinuousEngine(cached, n_slots=2, kv_pool_blocks=40, kv_block_size=BS,
                         kv_shadow=True, slot_max_seq=64)
    with pytest.raises(ValueError, match="one block of the selection"):
        ContinuousEngine(eng, n_slots=2, kv_pool_blocks=40, kv_block_size=16,
                         kv_shadow=False, slot_max_seq=64)
    for kw, what in ((dict(quant="int8"), "quantization"),
                     (dict(kv_quant="int8"), "int8 pool"),
                     (dict(mesh_cfg=MeshConfig(pp=2)), "meshes")):
        with pytest.raises(ValueError, match=what):
            create_engine("test-sala-tiny", seed=SEED, **kw)
    spec = create_engine("test-sala-tiny", seed=SEED, engine_cfg=EngineConfig(
        spec_decode=True, spec_draft_len=2))
    with pytest.raises(ValueError, match="speculative"):
        ContinuousEngine(spec, n_slots=2, kv_pool_blocks=40, kv_block_size=BS,
                         kv_shadow=False, slot_max_seq=64)
    out = eng.generate("w5 w6", max_tokens=2, chat=False)
    assert out["status"] == "failed" and "continuous engine" in out["error"]
