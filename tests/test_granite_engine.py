"""The granite_hybrid family through the served engine (ISSUE 51):
`engine/continuous.py` over the paged pool at `test-granite-tiny`, seeded
random weights. What the fleet delivers is held against the plain
reference's LOGITS (cellbench/reference/ssm_hybrid.py: each token's margin
below the reference's best, in logit-sigmas): chunked prefill beside decode
rows, then decode through pool, convolution state and matrix state; a prefix
hit restored from a snapshot of BOTH states against a cold run; a slot let
again while its neighbour carries on; the launch record's `state_rows` /
`state_fresh_rows` against a hand count; and what the family cannot take,
refused at start-up with a message.
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

REF = manifest.load_module("reference", "ssm_hybrid")
REF.Q_BLOCK = 16  # (the tiny sequences are a few blocks of the scan)
SEED, BS = 3, 8
CONFIG = manifest.load_json(
    os.path.join(ROOT, "tests", "data", "granite", "configs", "tiny-granite.json"))


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
            "test-granite-tiny", seed=SEED, attn_impl=impl, dtype=dtype,
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


def assert_logits(got, want, within=2e-3):
    """Logits against the reference's, in units of the logits' spread (the
    tied table is small: a logit row's standard deviation is ~0.002)."""
    assert np.abs(got - want).max() < within * want.std(), \
        (np.abs(got - want).max(), want.std())


def margins(prompt, gen, dtype="float32"):
    lg = ref_logits(prompt + gen, dtype)[len(prompt) - 1:len(prompt) + len(gen) - 1]
    return (lg.max(-1) - lg[np.arange(len(gen)), gen]) / lg.std()


@pytest.fixture(scope="module", params=["xla", "pallas"])
def fleet(request):
    return Fleet(impl=request.param)


def test_prefill_then_decode_through_the_pool_is_the_references_forward(fleet):
    """Two rows at once: a short one decodes while the other prefills 70
    tokens in chunks of at most 16 flat tokens beside its decode rows (the
    convolution and the scan carried over five launches) and decodes past it:
    every delivered token is the reference's top-1 (float32)."""
    short, long = prompt_ids(9, 1), prompt_ids(70, 2)
    a, b = fleet.ask_all([(short, 10), (long, 12)])
    for prompt, res in ((short, a), (long, b)):
        assert len(res["ids"]) >= 5
        np.testing.assert_allclose(margins(prompt, res["ids"]), 0.0, atol=1e-4)
    mixed = [r for r in fleet.records if r["phase"] == "mixed"]
    assert any(r["prefill_chunks"] and r["decode_rows"] for r in mixed)
    assert all("conv_tail_writes" not in r and "sparse_rows" not in r
               for r in fleet.records)  # (no tail a block, no selection)


def test_the_logits_themselves_are_the_references_launch_by_launch():
    """One ragged launch after another at the hooks' level, every flat
    token's logits handed back (tests/lfm2_util.launch): a 21-token chunk, a
    second chunk of the same row beside another row's first, then a decode
    token each: against the reference's full forward to float32 rounding."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from lfm2_util import launch

    cfg = get_model_config("test-granite-tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    pool = P.init_pool(cfg, 24, BS, n_slots=2, n_snapshots=2)
    table = np.zeros((2, 8), np.int32)
    table[0, :6], table[1, :4] = np.arange(1, 7), np.arange(7, 11)
    a, b = prompt_ids(37, 5), prompt_ids(13, 6)
    want_a, want_b = ref_logits(a), ref_logits(b)
    steps = [[(0, 0, a[:21], P.RAGGED_FIRST)],
             [(0, 21, a[21:36], P.RAGGED_PREFILL), (1, 0, b[:12], P.RAGGED_FIRST)],
             [(0, 36, a[36:], P.RAGGED_DECODE), (1, 12, b[12:], P.RAGGED_DECODE)]]
    for entries in steps:
        got, pool = launch(cfg, params, pool, table, entries)
        for (row, start, ids, _), lg in zip(entries, got):
            want = (want_a, want_b)[row][start:start + len(ids)]
            assert_logits(lg, want)


def test_a_hit_restored_from_a_snapshot_of_both_states_is_a_cold_run_exactly(fleet):
    """A second prompt shares 66 tokens of the first's 70: the hit is 64
    deep (the first left snapshots at 56 and 64), starts its row's
    convolution AND matrix states from the snapshot, and delivers what a
    fleet that never saw the first prompt delivers, token for token; against
    the reference at rounding."""
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
    events = {e: fleet.ce._bpx._m_snaps.labels(event=e).value
              for e in ("taken", "restored")}
    assert events["taken"] >= 3 and events["restored"] == 1
    assert "dli_state_snapshots_held" in fleet.eng.metrics.render()


def test_a_restored_row_reads_both_states_of_its_snapshot():
    """At the hooks' level, logits against the reference's: row 0 prefills 16
    tokens and leaves its states in snapshot 1; row 1, whose table shares
    those two blocks, starts at position 16 from that snapshot beside row 0's
    own next chunk, and both read the reference's logits. With the
    snapshot's convolution state zeroed the restored row's first tokens go
    wrong (the K - 1 inputs before position 16 are part of the state), and
    with its matrix state zeroed too every token does."""
    import jax

    from distributed_llm_inference_tpu.engine import paged as P
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config

    cfg = get_model_config("test-granite-tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    a = prompt_ids(34, 7)
    want = ref_logits(a)
    table = np.zeros((2, 8), np.int32)
    table[0, :5], table[1, :5] = np.arange(1, 6), [1, 2, 6, 7, 8]

    def run(pool, entries, restore, take):
        meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
            [(r, st, len(ids), kind) for r, st, ids, kind in entries], width=48, tile=8)
        toks = np.zeros((48,), np.int32)
        for (_, _, ids, _), off in zip(entries, offsets):
            toks[off:off + len(ids)] = ids
        snaps = (jnp.asarray(restore, jnp.int32), jnp.asarray(take, jnp.int32))
        x = M.embed(cfg, params, jnp.asarray(toks)[:, None], jnp.asarray(tok_pos))
        x, pool = M.forward_layers(
            cfg, params["layers"], x, pool, jnp.asarray(tok_pos),
            attn_hook=P.make_ragged_fill_hook(jnp.asarray(table), jnp.asarray(meta),
                                              jnp.asarray(tok_row), snaps),
            attn_seq_len=1)
        lg = np.asarray(M.unembed(cfg, params, x)[:, 0])
        return [lg[off:off + len(e[2])] for e, off in zip(entries, offsets)], pool

    pool = P.init_pool(cfg, 24, BS, n_slots=2, n_snapshots=2)
    (first,), pool = run(pool, [(0, 0, a[:16], P.RAGGED_FIRST)], [-1, -1], [1, -1])
    assert_logits(first, want[:16])
    second = [(0, 16, a[16:28], P.RAGGED_PREFILL), (1, 16, a[16:34], P.RAGGED_FIRST)]
    (own, restored), _ = run(pool, second, [-1, 1], [-1, -1])
    assert_logits(own, want[16:28])
    assert_logits(restored, want[16:34])
    blank = {**pool, "csnap": tuple(jnp.zeros_like(x) for x in pool["csnap"])}
    (_, no_conv), _ = run(blank, second, [-1, 1], [-1, -1])
    assert np.abs(no_conv[:3] - want[16:19]).max() > 0.5 * want.std()
    blank["snap"] = tuple(jnp.zeros_like(x) for x in pool["snap"])
    (own, cold), _ = run(blank, second, [-1, 1], [-1, -1])
    # (the decays let a missing state fade: every token is off, the first most)
    off = np.abs(cold - want[16:34]).max(axis=-1)
    assert off.min() > 0.1 * want.std() and off[:3].min() > want.std()
    assert_logits(own, want[16:28])


def test_a_slot_let_again_starts_from_zeros_while_its_neighbour_carries_on():
    """Two slots, three tenants: a long answer holds one slot while the
    other is let twice: the second tenant's states start from zeros, not
    from what the first left (its tokens are the reference's), the
    neighbour's are undisturbed, and each cold start is counted."""
    f = Fleet(slots=2)
    steady, first, second = prompt_ids(20, 8), prompt_ids(40, 9), prompt_ids(33, 10)
    out = {}
    t = threading.Thread(target=lambda: out.update(steady=f.ask(steady, 40)))
    t.start()
    f.ask(first, 6)
    res = f.ask(second, 8)
    t.join(300)
    np.testing.assert_allclose(margins(second, res["ids"]), 0.0, atol=1e-4)
    np.testing.assert_allclose(margins(steady, out["steady"]["ids"]), 0.0, atol=1e-4)
    assert len(out["steady"]["ids"]) >= 30
    assert f.ce._m_ssm_resets.value == 3
    assert f.ce._m_lin_resets.value == 0 and f.ce._m_conv_resets.value == 0
    assert "dli_ssm_state_resets_total 3" in f.eng.metrics.render()


def test_the_launch_records_counts_are_the_hand_count():
    """One 70-token prompt alone, 24 flat tokens a step (one decode tile is
    reserved): chunks of 16 tokens, cut at 56 and 64 where the snapshots are
    due: a state row a chunk, fresh in the first alone; then the answer's
    decode steps, a row-step each; the state-row counter is their sum."""
    f = Fleet()
    prompt = prompt_ids(70, 11)
    f.ask(prompt, 5)
    mixed = [r for r in f.records if r["phase"] == "mixed" and r["prefill_tokens"]]
    ends = np.cumsum([r["prefill_tokens"] for r in mixed]).tolist()
    assert ends[-1] == 70 and 56 in ends and 64 in ends
    assert sum(r["state_snapshots_taken"] for r in mixed) == 2
    assert [r["state_rows"] for r in mixed] == [1] * len(mixed)
    assert [r["state_fresh_rows"] for r in mixed] == [1] + [0] * (len(mixed) - 1)
    assert mixed[0]["conv_state_resets"] == 1 and mixed[0]["state_restored_tokens"] == 0
    # the answer's 5 tokens: the first off the last chunk, 4 decode row-steps
    decode = [r for r in f.records if not r["prefill_tokens"]]
    assert sum(r["state_rows"] for r in decode) == 4
    assert any(r["phase"] == "chunk" for r in decode)
    assert all(r["state_fresh_rows"] == 0 for r in decode)
    touched = f.ce._m_lin_rows.labels(state="touched").value
    assert touched == sum(r["state_rows"] for r in f.records) == len(mixed) + 4
    # a prefix hit's first chunk is fresh too, and restores its 64 tokens
    f.records.clear()
    f.ask(prompt[:66] + prompt_ids(9, 12), 3)
    first = [r for r in f.records if r["phase"] == "mixed" and r["prefill_tokens"]][0]
    assert (first["state_fresh_rows"], first["state_restored_tokens"],
            first["conv_state_resets"]) == (1, 64, 0)
    held = f.ce._m_lin_rows.labels(state="held").value
    assert held >= f.ce._m_lin_rows.labels(state="touched").value > touched


@pytest.fixture(scope="module")
def wide_fleets():
    """32 slots of the tiny model: their float32 states outweigh its weights,
    so the launch is the fleet's 32 tiles of 8 and the dense 128 on top, 384
    wide, and its token-wise layers run on 32 + 2 x 128 = 288 live tokens
    (engine/scheduler.live_width); beside it the same fleet held to the tile
    layout throughout (`live_width` = the width). Both serve the same
    requests at once: prompts that land in chunks beside decoding rows, a
    prefix hit restored from a snapshot, slots let again."""
    from distributed_llm_inference_tpu.engine import scheduler

    asks = [(prompt_ids(9 + 11 * i, 20 + i), 6 + i % 5) for i in range(8)]
    again = (asks[6][0][:66] + prompt_ids(7, 40), 5)

    def serve():
        f = Fleet(impl="pallas", budget=None, slots=32, pool=700, snapshots=24)
        first = f.ask_all(asks)
        return f, first + [f.ask(*again)] + f.ask_all(asks[:3])

    compact = serve()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scheduler, "live_width", scheduler.step_width)
        tiles = serve()
    return asks + [again] + asks[:3], compact, tiles


def test_the_live_tokens_alone_deliver_what_the_tile_layout_delivers(wide_fleets):
    asks, (compact, got), (tiles, want) = wide_fleets
    assert compact.ce.stats()["scheduler"]["step_width"] == 384
    assert compact.ce.stats()["scheduler"]["live_width"] == 288
    assert tiles.ce.stats()["scheduler"]["live_width"] == 384
    assert got[8].get("prefix_cached_tokens") == want[8].get("prefix_cached_tokens") == 64
    for (prompt, _), a, b in zip(asks, got, want):
        assert a["ids"] == b["ids"] and len(a["ids"]) >= 3
        np.testing.assert_allclose(margins(prompt, a["ids"]), 0.0, atol=1e-4)


def test_the_record_and_the_counter_hold_the_live_and_the_computed_tokens(wide_fleets):
    _, (compact, _), (tiles, _) = wide_fleets
    for f, computed in ((compact, 288), (tiles, 384)):
        mixed = [r for r in f.records if r["phase"] == "mixed"]
        assert any(r["prefill_chunks"] and r["decode_rows"] for r in mixed)
        assert all(r["tokens_live"] == r["decode_rows"] + r["prefill_tokens"] <= computed
                   for r in mixed)
        assert {r["tokens_computed"] for r in mixed} == {computed}
        assert all(r["tiles"] == 48 for r in mixed)
        count = {s: f.ce._m_mixed_tokens[s].value for s in ("live", "computed")}
        assert count == {"live": sum(r["tokens_live"] for r in mixed),
                         "computed": computed * len(mixed)}
        assert "tokens_live" not in next(r for r in f.records if r["phase"] == "chunk")


def test_start_up_refuses_what_the_family_does_not_carry():
    eng = create_engine("test-granite-tiny", seed=SEED)
    with pytest.raises(ValueError, match="no dense fleet of convolution and matrix"):
        ContinuousEngine(eng, n_slots=2)
    cached = create_engine("test-granite-tiny", seed=SEED,
                           engine_cfg=EngineConfig(prefix_cache_entries=8))
    with pytest.raises(ValueError, match="state snapshots behind"):
        ContinuousEngine(cached, n_slots=2, kv_pool_blocks=40, kv_block_size=BS,
                         kv_shadow=True, slot_max_seq=64)
    spec = create_engine("test-granite-tiny", seed=SEED,
                         engine_cfg=EngineConfig(spec_decode=True, spec_draft_len=2))
    with pytest.raises(ValueError, match="state-space layer's states"):
        ContinuousEngine(spec, n_slots=2, kv_pool_blocks=40, kv_block_size=BS,
                         kv_shadow=False, slot_max_seq=64)
    for kw, what in ((dict(quant="int8"), "weight quantization"),
                     (dict(kv_quant="int8"), "int8 pool"),
                     (dict(mesh_cfg=MeshConfig(pp=2)), "meshes")):
        with pytest.raises(ValueError, match=what):
            create_engine("test-granite-tiny", seed=SEED, **kw)
    out = eng.generate("w5 w6", max_tokens=2, chat=False)
    assert out["status"] == "failed" and "continuous engine" in out["error"]
