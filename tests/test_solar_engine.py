"""The solar_open2 family through the served engine (ISSUE 57):
`engine/continuous.py` over the paged pool at `test-solar-tiny`, seeded
random weights: the first configuration that is BOTH recurrent (a snapshot a
prompt, `state_rows` in the launch record) AND routed over a share (the
"routed" counts, `moe_pairs{where}`). What the fleet delivers is held against
the plain reference's LOGITS (cellbench/reference/delta_hybrid_moe.py: each
token's margin below the reference's best, in logit-sigmas): chunked prefill
beside decode rows, then decode through pool, convolution states and matrix
state; a prefix hit restored from ONE snapshot of matrix AND convolution
states against a cold run; a slot let again while its neighbour carries on; a
preempted row; the launch record's counts against a hand count; the eight
shares' sum; and what the family cannot take, refused at start-up.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import solar_util as U
from distributed_llm_inference_tpu import EngineConfig, MeshConfig, create_engine
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models.registry import get_model_config

SEED, BS = 3, 8
CFG = get_model_config("test-solar-tiny")


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


def ref_logits(seq, cfg=CFG):
    return U.ref_logits(cfg, SEED, seq)


class Fleet:
    def __init__(self, impl="xla", budget=24, slots=2, pool=64, chunk=4,
                 snapshots=6, overrides=None, **kw):
        self.eng = create_engine(
            "test-solar-tiny", seed=SEED, attn_impl=impl, dtype="float32",
            engine_cfg=EngineConfig(prefix_cache_entries=8, step_token_budget=budget,
                                    state_snapshots=snapshots),
            **({"model_overrides": overrides} if overrides else {}))
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
    """Logits against the reference's, in units of the logits' spread."""
    assert np.abs(got - want).max() < within * want.std(), \
        (np.abs(got - want).max(), want.std())


def margins(prompt, gen, cfg=CFG):
    lg = ref_logits(prompt + gen, cfg)[len(prompt) - 1:len(prompt) + len(gen) - 1]
    return (lg.max(-1) - lg[np.arange(len(gen)), gen]) / lg.std()


@pytest.fixture(scope="module", params=["xla", "pallas"])
def fleet(request):
    return Fleet(impl=request.param)


def test_prefill_then_decode_through_the_pool_is_the_references_forward(fleet):
    """Two rows at once: a short one decodes while the other prefills 70
    tokens in chunks of at most 16 flat tokens beside its decode rows (three
    convolutions and the delta rule carried over five launches) and decodes
    past it: every delivered token is the reference's top-1 (float32), and
    every launch counts what it routed."""
    short, long = prompt_ids(9, 1), prompt_ids(70, 2)
    a, b = fleet.ask_all([(short, 10), (long, 12)])
    for prompt, res in ((short, a), (long, b)):
        assert len(res["ids"]) >= 5
        np.testing.assert_allclose(margins(prompt, res["ids"]), 0.0, atol=1e-4)
    mixed = [r for r in fleet.records if r["phase"] == "mixed"]
    assert any(r["prefill_chunks"] and r["decode_rows"] for r in mixed)
    assert all("conv_tail_writes" not in r and "sparse_rows" not in r
               for r in fleet.records)  # (no tail a block, no selection)
    assert all(r["delta_chunks"] >= r["state_rows"] for r in fleet.records)
    # every layer routes 2 experts a live token, all 8 held here
    pairs = fleet.ce._m_moe_pairs.labels(where="routed").value
    assert pairs > 0 and fleet.ce._routed_shape == (2, 4, 8)


def test_a_hit_restored_from_one_snapshot_of_all_states_is_a_cold_run_exactly(fleet):
    """A second prompt shares 66 tokens of the first's 70: the hit is 64
    deep (the first left snapshots at 56 and 64), starts its row's three
    convolution states AND three matrix states from the ONE snapshot, and
    delivers what a fleet that never saw the first prompt delivers, token
    for token; against the reference at rounding."""
    base = prompt_ids(70, 3)
    again = base[:66] + prompt_ids(9, 4)
    fleet.ask(base, 6)
    held = fleet.ce._bpx.snap_stats()["held"]
    fleet.records.clear()
    hit = fleet.ask(again, 10)
    assert hit.get("prefix_cached_tokens") == 64 and held >= 2
    cold = Fleet(impl=fleet.eng.cfg.attn_impl).ask(again, 10)
    assert cold.get("prefix_cached_tokens", 0) == 0
    assert hit["ids"] == cold["ids"]
    np.testing.assert_allclose(margins(again, hit["ids"]), 0.0, atol=1e-4)
    events = {e: fleet.ce._bpx._m_snaps.labels(event=e).value
              for e in ("taken", "restored")}
    assert events["taken"] >= 3 and events["restored"] == 1
    first = [r for r in fleet.records if r["phase"] == "mixed" and r["prefill_tokens"]][0]
    assert (first["state_snapshots_restored"], first["state_restored_tokens"]) == (1, 64)


def _run(cfg, params, pool, table, entries, restore, take, width=48):
    meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
        [(r, st, len(ids), kind) for r, st, ids, kind in entries], width=width, tile=8)
    toks = np.zeros((width,), np.int32)
    for (_, _, ids, _), off in zip(entries, offsets):
        toks[off:off + len(ids)] = ids
    snaps = (jnp.asarray(restore, jnp.int32), jnp.asarray(take, jnp.int32))
    x = M.embed(cfg, params, jnp.asarray(toks)[:, None], jnp.asarray(tok_pos))
    x, pool = M.forward_layers(
        cfg, params["layers"], x, P._routed_reset(pool), jnp.asarray(tok_pos),
        attn_hook=P.make_ragged_fill_hook(jnp.asarray(table), jnp.asarray(meta),
                                          jnp.asarray(tok_row), snaps),
        attn_seq_len=1)
    lg = np.asarray(M.unembed(cfg, params, x)[:, 0])
    return [lg[off:off + len(e[2])] for e, off in zip(entries, offsets)], pool


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_logits_themselves_are_the_references_launch_by_launch(impl):
    """One ragged launch after another at the hooks' level, every flat
    token's logits handed back (tests/lfm2_util.launch): a 21-token chunk, a
    second chunk of the same row beside another row's first, then a decode
    token each: against the reference's full forward to float32 rounding."""
    cfg = CFG.replace(attn_impl=impl)
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
        got, pool = U.launch(cfg, params, pool, table, entries)
        for (row, start, ids, _), lg in zip(entries, got):
            assert_logits(lg, (want_a, want_b)[row][start:start + len(ids)])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_decode_step_is_the_one_token_form_between_two_mixed_launches(impl, monkeypatch):
    """The hand-over at the hooks' level (ISSUE 58): two rows prefill in a
    ragged launch (the chunked form, a call a KDA layer), decode through the
    decode program's hook (`engine/paged._forward_step_paged`: the one-token
    form, a call a KDA layer and none of the chunked form; row 1 sits out the
    later steps and its states wait), then row 0's next chunk rides a ragged
    launch again beside row 1's decode token: every launch's logits are the
    reference's, whichever form wrote the state the launch starts from."""
    from distributed_llm_inference_tpu.models import solar_open2 as SO

    calls = {"step": 0, "rows": 0}

    def counting(name, fn):
        def wrapped(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return wrapped

    monkeypatch.setattr(SO, "delta_rule_step", counting("step", SO.delta_rule_step))
    monkeypatch.setattr(SO, "delta_rule_rows", counting("rows", SO.delta_rule_rows))
    cfg = CFG.replace(attn_impl=impl)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    pool = P.init_pool(cfg, 24, BS, n_slots=2, n_snapshots=2)
    table = np.zeros((2, 8), np.int32)
    table[0, :6], table[1, :4] = np.arange(1, 7), np.arange(7, 11)
    a, b = prompt_ids(40, 15), prompt_ids(16, 16)
    want = ref_logits(a), ref_logits(b)
    first = [(0, 0, a[:21], P.RAGGED_FIRST), (1, 0, b[:12], P.RAGGED_FIRST)]
    got, pool = U.launch(cfg, params, pool, table, first, retrace=True)
    for (row, start, ids, _), lg in zip(first, got):
        assert_logits(lg, want[row][start:start + len(ids)])
    assert calls == {"step": 0, "rows": 3}
    for t in range(6):  # row 1 carries a token in the first three steps only
        active = np.array([True, t < 3])
        toks = np.array([[a[21 + t]], [b[min(12 + t, 15)]]], np.int32)
        pos = np.array([21 + t, min(12 + t, 15)], np.int32)
        lg, pool = P._forward_step_paged(
            cfg, params, jnp.asarray(toks), pool, jnp.asarray(table),
            jnp.asarray(pos), active=jnp.asarray(active))
        assert_logits(np.asarray(lg)[0], want[0][21 + t])
        if active[1]:
            assert_logits(np.asarray(lg)[1], want[1][12 + t])
    assert calls == {"step": 18, "rows": 3}
    last = [(0, 27, a[27:40], P.RAGGED_PREFILL), (1, 15, b[15:], P.RAGGED_DECODE)]
    got, pool = U.launch(cfg, params, pool, table, last, retrace=True)
    for (row, start, ids, _), lg in zip(last, got):
        assert_logits(lg, want[row][start:start + len(ids)])
    assert calls == {"step": 18, "rows": 6}


def test_a_restored_row_reads_every_state_of_its_snapshot():
    """At the hooks' level, logits against the reference's: row 0 prefills 16
    tokens and leaves its states in snapshot 1; row 1, whose table shares
    those two blocks, starts at position 16 from that snapshot beside row 0's
    own next chunk, and both read the reference's logits. With the
    snapshot's convolution states zeroed the restored row's first tokens go
    wrong (the K - 1 inputs before position 16 are part of the state), and
    with its matrix states zeroed too every token does."""
    cfg = CFG
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    a = prompt_ids(34, 7)
    want = ref_logits(a)
    table = np.zeros((2, 8), np.int32)
    table[0, :5], table[1, :5] = np.arange(1, 6), [1, 2, 6, 7, 8]
    pool = P.init_pool(cfg, 24, BS, n_slots=2, n_snapshots=2)
    assert [x.shape for x in pool["csnap"]] == [(2, 3, 768)] * 3
    assert [(x.shape, x.dtype) for x in pool["snap"]] == [((2, 2, 128, 128), jnp.float32)] * 3
    (first,), pool = _run(cfg, params, pool, table, [(0, 0, a[:16], P.RAGGED_FIRST)],
                          [-1, -1], [1, -1])
    assert_logits(first, want[:16])
    second = [(0, 16, a[16:28], P.RAGGED_PREFILL), (1, 16, a[16:34], P.RAGGED_FIRST)]
    (own, restored), _ = _run(cfg, params, pool, table, second, [-1, 1], [-1, -1])
    assert_logits(own, want[16:28])
    assert_logits(restored, want[16:34])
    blank = {**pool, "csnap": tuple(jnp.zeros_like(x) for x in pool["csnap"])}
    (_, no_conv), _ = _run(cfg, params, blank, table, second, [-1, 1], [-1, -1])
    assert np.abs(no_conv[:3] - want[16:19]).max() > 0.02 * want.std()
    blank["snap"] = tuple(jnp.zeros_like(x) for x in pool["snap"])
    (own, cold), _ = _run(cfg, params, blank, table, second, [-1, 1], [-1, -1])
    assert np.abs(cold - want[16:34]).max(axis=-1).min() > 0.02 * want.std()
    assert_logits(own, want[16:28])


def test_a_slot_let_again_starts_from_zeros_while_its_neighbour_carries_on():
    """Two slots, three tenants: a long answer holds one slot while the
    other is let twice: the second tenant's states start from zeros, not
    from what the first left (its tokens are the reference's), the
    neighbour's are undisturbed, and each cold start is counted."""
    f = Fleet(slots=2)
    steady, first, second = prompt_ids(20, 28), prompt_ids(40, 9), prompt_ids(33, 10)
    out = {}
    t = threading.Thread(target=lambda: out.update(steady=f.ask(steady, 40)))
    t.start()
    f.ask(first, 6)
    res = f.ask(second, 8)
    t.join(300)
    np.testing.assert_allclose(margins(second, res["ids"]), 0.0, atol=1e-4)
    np.testing.assert_allclose(margins(steady, out["steady"]["ids"]), 0.0, atol=1e-4)
    assert len(out["steady"]["ids"]) >= 30
    assert f.ce._m_state_resets.value == 3


def test_a_preempted_row_comes_back_as_it_was():
    """One slot, a batch-class answer under way when an interactive request
    arrives: the row is preempted (no shadow store: its prompt and what it
    had generated are prefilled again, the states with them) and both
    answers are the reference's."""
    f = Fleet(slots=1, pool=48)
    slow, quick = prompt_ids(30, 13), prompt_ids(12, 14)
    out = {}

    def ask(name, ids, mt, slo):
        out[name] = f.ce.submit(words(ids), max_tokens=mt, greedy=True, chat=False,
                                slo_class=slo)

    t = threading.Thread(target=ask, args=("slow", slow, 40, "batch"))
    t.start()
    while not any(r["phase"] == "chunk" for r in f.records):
        threading.Event().wait(0.01)
    ask("quick", quick, 6, "interactive")
    t.join(300)
    for name, prompt in (("slow", slow), ("quick", quick)):
        res = out[name]
        assert res.get("status") == "success", res
        ids = WordTok().encode(res["response"])
        assert len(ids) >= 5
        np.testing.assert_allclose(margins(prompt, ids), 0.0, atol=1e-4)


def test_the_launch_records_counts_are_the_hand_count():
    """One 70-token prompt alone, 24 flat tokens a step (one decode tile is
    reserved): chunks of 16 tokens, cut at 56 and 64 where the snapshots are
    due: a state row a chunk, fresh in the first alone, each chunk on its
    own tiles from flat place 0 (one chunk of the delta rule's 64); then the
    answer's decode steps, a row-step and a chunk each; the counters are
    their sums, by phase."""
    f = Fleet()
    prompt = prompt_ids(70, 11)
    f.ask(prompt, 5)
    mixed = [r for r in f.records if r["phase"] == "mixed" and r["prefill_tokens"]]
    ends = np.cumsum([r["prefill_tokens"] for r in mixed]).tolist()
    assert ends[-1] == 70 and 56 in ends and 64 in ends
    assert sum(r["state_snapshots_taken"] for r in mixed) == 2
    assert [r["state_rows"] for r in mixed] == [1] * len(mixed)
    assert [r["delta_chunks"] for r in mixed] == [1] * len(mixed)
    assert [r["state_fresh_rows"] for r in mixed] == [1] + [0] * (len(mixed) - 1)
    decode = [r for r in f.records if not r["prefill_tokens"]]
    assert sum(r["state_rows"] for r in decode) == 4
    assert sum(r["delta_chunks"] for r in decode) == 4
    assert any(r["phase"] == "chunk" for r in decode)
    rows = {p: f.ce._m_delta_rows.labels(phase=p).value for p in ("mixed", "chunk")}
    chunks = {p: f.ce._m_delta_chunks.labels(phase=p).value for p in ("mixed", "chunk")}
    assert rows == chunks == {
        p: sum(r["state_rows"] for r in f.records if r["phase"] == p)
        for p in ("mixed", "chunk")}
    assert "dli_delta_chunks_total" in f.eng.metrics.render()


def test_a_wide_launch_cuts_a_row_into_the_delta_rules_chunks():
    """A 150-token prompt in one 160-token launch: three chunks of 64 places
    for one state row, by the record and by the counter."""
    f = Fleet(budget=160, pool=80)
    prompt = prompt_ids(150, 15)
    res = f.ask(prompt, 4)
    np.testing.assert_allclose(margins(prompt, res["ids"]), 0.0, atol=1e-4)
    mixed = [r for r in f.records if r["phase"] == "mixed" and r["prefill_tokens"]]
    assert max(r["delta_chunks"] for r in mixed) >= 2
    assert sum(r["delta_chunks"] for r in mixed) > sum(r["state_rows"] for r in mixed)


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(ep):
    """The guide's section 4 for a share: for one routed layer, the `ep`
    shares' routed parts (each holding 8 / ep published experts of the same
    seed) plus the shared expert counted once are the uncut reference's
    layer, and every token-expert pair is computed in exactly one share."""
    from distributed_llm_inference_tpu.models import stack

    cfg, layer = CFG, 2
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.dim), jnp.float32)
    full = U.ref_params(cfg, SEED)
    lp = {n: full[n][layer] for n in U.REF.FFN_LEAVES}
    router = dict(k=cfg.n_experts_per_tok, renorm=True, scaling=cfg.routed_scaling,
                  norm_eps=cfg.router_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = U.REF.moe_ffn(h[0], lp, lo=0, **router)
        total = U.REF._swiglu(h[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    held, pairs = 8 // ep, 0
    for lo in range(0, 8, held):
        part = cfg.replace(name=f"share{lo}", expert_lo=lo, n_experts_held=held)
        p = M.init_params(part, jax.random.PRNGKey(SEED))["layers"]["moe"]
        row = {n: leaf[layer] for n, leaf in p.items() if n not in stack.BANKS}
        out, sizes, away = stack.moe_ffn(
            part, row, {n: p[n] for n in stack.BANKS}, layer, h)
        with jax.default_matmul_precision("highest"):
            mine = stack.swiglu(h[0], row["ws_gate"], row["ws_up"], row["ws_down"])
        total = total + (out[0] - mine)
        pairs += int(sizes.sum())
        assert int(sizes.sum()) + int(away) == 24 * cfg.n_experts_per_tok
    assert pairs == 24 * cfg.n_experts_per_tok  # every pair in exactly one share
    assert np.abs(np.asarray(total - want)).max() < 2e-5 * max(
        1.0, float(np.abs(np.asarray(want)).max()))


def test_start_up_refuses_what_the_family_does_not_carry():
    eng = create_engine("test-solar-tiny", seed=SEED)
    with pytest.raises(ValueError, match="no dense fleet of convolution and matrix"):
        ContinuousEngine(eng, n_slots=2)
    cached = create_engine("test-solar-tiny", seed=SEED,
                           engine_cfg=EngineConfig(prefix_cache_entries=8))
    with pytest.raises(ValueError, match="state snapshots behind"):
        ContinuousEngine(cached, n_slots=2, kv_pool_blocks=40, kv_block_size=BS,
                         kv_shadow=True, slot_max_seq=64)
    spec = create_engine("test-solar-tiny", seed=SEED,
                         engine_cfg=EngineConfig(spec_decode=True, spec_draft_len=2))
    with pytest.raises(ValueError, match="already be in"):
        ContinuousEngine(spec, n_slots=2, kv_pool_blocks=40, kv_block_size=BS,
                         kv_shadow=False, slot_max_seq=64)
    for kw, what in ((dict(quant="int8"), "weight quantization"),
                     (dict(kv_quant="int8"), "int8 pool"),
                     (dict(mesh_cfg=MeshConfig(pp=2)), "meshes"),
                     (dict(mesh_cfg=MeshConfig(ep=2)), "meshes")):
        with pytest.raises(ValueError, match=what):
            create_engine("test-solar-tiny", seed=SEED, **kw)
    out = eng.generate("w5 w6", max_tokens=2, chat=False)
    assert out["status"] == "failed" and "continuous engine" in out["error"]
