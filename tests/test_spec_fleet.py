"""Speculative decoding on the ragged paged fleet (ISSUES 13 + 15).

The bar: draft-then-verify inside the mixed launch is a LAUNCH strategy,
not a semantics change — greedy output must be bit-identical to
non-speculative decode (threaded fleets, warm prefix reuse, crash and
preemption landing mid-spec-cycle included), speculated tokens must
debit step_token_budget so the SLO layer can throttle K to 0 under TPOT
pressure, decode rows stay reserved ahead of prefill chunks, and the
whole accept/reject decision stays traced (the spec-mixed HLO checks
pin the artifact half).

Device-derived launch metadata (ISSUE 15): decode/verify q_start and
positions come from the device-resident slot state, so an unfetched
verify row never freezes its slot — verify rows launch EVERY step, back
to back (pinned by the pipelined-launch count: >0), greedy output stays
bit-identical to the plain fleet, and per-slot adaptive K
(acceptance-rate EWMA) sizes each draft between 0 and spec_draft_len.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as EP
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.scheduler import (
    SLOClass,
    TokenBudgetScheduler,
    ngram_draft,
)
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.utils import faults

TILE = 8
SERVE_CFG = dict(dtype="float32", eos_token_id=-1, max_seq_len=512)

# byte-fallback tokenization makes word repeats literal token repeats,
# so the bigram planner finds drafts and the model (even a random-weight
# tiny one) verifies SOME of them on a fully periodic stream
REPEAT_PROMPT = "the cat sat on the mat " * 10
MIXED_PROMPTS = [
    REPEAT_PROMPT,
    "the quick brown fox jumps over the lazy dog",
    "abc xyz " * 14,
    "short",
]


@pytest.fixture(scope="module")
def setup():
    cfg = get_model_config("test-llama-tiny", **SERVE_CFG)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(autouse=True)
def _always_disarm():
    faults.disarm()
    yield
    faults.disarm()


def _cont(cfg, params, spec, **kw):
    ecfg = dict(
        prefix_cache_entries=4, chunked_prefill=True,
        step_token_budget=64, prefill_buckets=(64, 128, 256),
        spec_decode=spec, spec_draft_len=4 if spec else 0,
    )
    ecfg.update(kw.pop("engine_cfg", {}))
    eng = InferenceEngine(cfg, params=params, engine_cfg=EngineConfig(**ecfg))
    args = dict(n_slots=4, chunk_steps=8, slot_max_seq=512,
                kv_pool_blocks=120, kv_block_size=16,
                restart_backoff_s=0.01)
    args.update(kw)
    return ContinuousEngine(eng, **args)


# -- planner units (no engine, no device) ------------------------------------

def _sched(width=64, n_slots=4):
    classes = {
        "interactive": SLOClass("interactive", 0.5, 0.1, 4.0, True),
        "standard": SLOClass("standard", 2.0, 0.5, 2.0, True),
    }
    return TokenBudgetScheduler(classes, "standard", width, TILE, n_slots)


def test_ngram_draft_rules():
    # most recent earlier occurrence of the current bigram wins
    hist = [1, 2, 3, 9, 1, 2, 5, 6, 1, 2]
    assert ngram_draft(hist, 3) == [5, 6, 1]
    # no earlier occurrence -> NO draft (plain decode row, zero cost)
    assert ngram_draft([1, 2, 3, 4, 5], 3) == []
    # short histories never draft
    assert ngram_draft([1, 2], 2) == []
    assert ngram_draft(hist, 0) == []
    # a draft near the end of the history may be short, never empty
    assert ngram_draft([7, 8, 7, 8], 4) == [7, 8]
    # short-period repetition: an earlier match supplies the FULL draft
    # where the latest occurrence truncates at the history end
    assert ngram_draft([9, 9, 9, 9, 9, 9], 4) == [9, 9, 9, 9]
    assert ngram_draft([1, 2, 1, 2, 1, 2, 1, 2], 4) == [1, 2, 1, 2]


def test_spec_tokens_debit_step_token_budget():
    """A verify row reserves ceil((1+K)/tile) tiles out of the same
    budget prefill chunks draw from — with a fat draft the pending job
    gets strictly fewer tiles than with plain decode rows."""
    import test_scheduler as TS

    sched = _sched(width=64)  # 8 tiles
    cls = sched.classes["standard"]
    job = TS._job(cls, tail=64, enqueued=0.0)
    # plain: 4 decode rows = 4 tiles -> 4 tiles (32 tokens) for prefill
    plain = sched.plan(4, [job], now=10.0)
    assert plain == [(job, 32)]
    # speculative: 4 verify rows of 1+15 tokens = 2 tiles each -> 8
    # tiles of decode reservation... clamp: spec_draft_len would never
    # plan that; use 3 spec rows of 2 tiles + 1 plain = 7 tiles -> 1
    spec = sched.plan(3 * 2 + 1, [job], now=10.0)
    assert spec == [(job, 8)]


def test_spec_draft_len_throttles_to_zero_under_tpot_pressure():
    sched = _sched()
    assert sched.spec_draft_len(4, 2, 1, active_classes={"standard"}) == 4
    # observed TPOT over the class target: the SAME decode-protection
    # signal that halves the prefill budget disables speculation
    sched.observe("standard", 0.01, 5.0)
    assert sched.spec_draft_len(4, 2, 1, active_classes={"standard"}) == 0
    # other classes under target keep speculating
    assert sched.spec_draft_len(4, 2, 1, active_classes=set()) == 4


def test_spec_draft_len_fits_the_step_budget():
    sched = _sched(width=64, n_slots=4)  # 8 tiles
    # 4 verify rows must coexist with one prefill-progress tile: K=7
    # keeps each row at one tile (1+7 <= tile)
    assert sched.spec_draft_len(7, 4, 0, jobs_pending=True) == 7
    # K=15 would need 2 tiles per row (8 + 1 > 8) -> shrink until it fits
    assert sched.spec_draft_len(15, 4, 0, jobs_pending=True) == 7
    # fewer rows leave room for fatter drafts
    assert sched.spec_draft_len(15, 3, 0, jobs_pending=True) == 15
    assert sched.spec_draft_len(0, 4, 0) == 0
    assert sched.spec_draft_len(4, 0, 4) == 0


def test_decode_rows_reserved_before_prefill_with_spec():
    """Verify rows never starve prefill liveness and vice versa: even
    with the decode reservation at budget, the oldest job still gets a
    tile — and decode tiles were reserved FIRST."""
    import test_scheduler as TS

    sched = _sched(width=64)
    cls = sched.classes["standard"]
    job = TS._job(cls, tail=64, enqueued=0.0)
    out = sched.plan(7, [job], now=10.0)  # 7 of 8 tiles to decode/spec
    assert out == [(job, 8)]


# -- adaptive per-slot K (acceptance-EWMA throttle, ISSUE 15) ----------------

def test_adaptive_k_converges_down_and_reprobes():
    """A slot whose drafts keep rejecting degrades to K=0 (plain decode
    rows — no verify tiles burnt) and re-probes with a 1-token draft
    after SPEC_REPROBE skipped plans."""
    from distributed_llm_inference_tpu.engine.scheduler import SPEC_REPROBE

    sched = _sched()
    assert sched.spec_slot_k(0, 4) == 4  # no data: probe at full depth
    for _ in range(8):
        sched.observe_spec(0, 4, 0)
    # re-probe: after SPEC_REPROBE consecutive skipped plans, one
    # 1-token draft goes out so a stream that turns repetitive recovers
    ks = [sched.spec_slot_k(0, 4) for _ in range(SPEC_REPROBE)]
    assert ks[-1] == 1 and all(k == 0 for k in ks[:-1])
    # the probe reset the skip counter: the next plan skips again
    assert sched.spec_slot_k(0, 4) == 0


def test_adaptive_k_converges_back_up():
    sched = _sched()
    for _ in range(8):
        sched.observe_spec(0, 4, 0)
    assert sched.spec_slot_k(0, 4) == 0
    for _ in range(16):
        sched.observe_spec(0, 4, 4)  # full acceptance again
    assert sched.spec_slot_k(0, 4) == 4
    # partial acceptance sizes the draft proportionally, never 0
    sched2 = _sched()
    for _ in range(16):
        sched2.observe_spec(1, 4, 2)
    assert 1 <= sched2.spec_slot_k(1, 4) <= 3


def test_adaptive_k_is_per_slot_and_resettable():
    sched = _sched()
    for _ in range(8):
        sched.observe_spec(0, 4, 0)
    assert sched.spec_slot_k(0, 4) == 0
    assert sched.spec_slot_k(1, 4) == 4  # untouched slot unaffected
    sched.spec_reset(0)  # new tenant on the slot: history forgotten
    assert sched.spec_slot_k(0, 4) == 4


def test_adaptive_k_tpot_pressure_still_forces_zero():
    """The global TPOT-pressure gate runs BEFORE the per-slot EWMA: a
    perfectly-accepting slot still drafts nothing under decode
    pressure (engine/continuous clamps kb = min(spec_draft_len(...),
    spec_slot_k(...)))."""
    sched = _sched()
    for _ in range(8):
        sched.observe_spec(0, 4, 4)
    assert sched.spec_slot_k(0, 4) == 4
    sched.observe("standard", 0.01, 5.0)  # TPOT over target
    assert sched.spec_draft_len(4, 1, 0, active_classes={"standard"}) == 0


def test_spec_block_cap_pessimistic_frontier():
    """The allocation clamp under back-to-back verify rows: the device
    may lead the lagged host position by every pending launch's maximum
    advance, so the cap must use the pessimistic frontier."""
    from distributed_llm_inference_tpu.engine.scheduler import spec_block_cap

    # 4 blocks of 16 = positions 0..63; at host pos 50 with nothing
    # pending a draft may extend to position 62 (write at pos..pos+k)
    assert spec_block_cap(4, 16, 50) == 13
    # two pending verify launches of 4 drafts each could have advanced
    # the device by up to 2 * (4 + 1): the cap shrinks accordingly
    assert spec_block_cap(4, 16, 50 + 2 * 5) == 3
    # at/near the allocation end the cap goes non-positive -> no draft
    assert spec_block_cap(4, 16, 63) <= 0


# -- traced verify unit (device math vs a slot_step simulation) --------------

def _simulate_plain(cfg, tokens, remaining):
    """Reference: what slot_step's greedy bookkeeping does with this
    emission stream, one token per step."""
    emitted, pos_adv, rem = [], 0, remaining
    for t in tokens:
        stop = t in cfg.all_stop_ids
        can_emit = not stop and rem > 0
        pos_adv += 1
        if stop:
            return emitted, pos_adv, rem, False, 0
        if rem <= 0:
            break
        emitted.append(t)
        rem -= 1
        if rem == 0:
            return emitted, pos_adv, rem, False, t
    return emitted, pos_adv, rem, True, emitted[-1] if emitted else 0


@pytest.mark.parametrize(
    "window,draft,n_draft,remaining",
    [
        ([5, 6, 7, 8, 9], [5, 6, 7, 8], 4, 20),   # full accept + bonus
        ([5, 6, 7, 8, 9], [5, 9, 7, 8], 4, 20),   # partial accept
        ([5, 6, 7, 8, 9], [1, 2, 3, 4], 4, 20),   # all rejected
        ([5, 2, 7, 8, 9], [5, 2, 7, 8], 4, 20),   # EOS (id 2) mid-window
        ([2, 6, 7, 8, 9], [5, 6, 7, 8], 4, 20),   # EOS first
        ([5, 6, 7, 8, 9], [5, 6, 7, 8], 4, 3),    # budget clamps
        ([5, 6, 2, 8, 9], [5, 6, 2, 8], 4, 2),    # budget before the EOS
        ([5, 6, 7, 8, 9], [5, 6, 0, 0], 2, 20),   # short draft
    ],
)
def test_spec_verify_matches_slot_step_semantics(window, draft, n_draft,
                                                 remaining):
    cfg = get_model_config("test-llama-tiny")  # eos_token_id = 2
    state, _ = G.init_slots(1, cfg.vocab_size)
    state = state._replace(
        active=jnp.ones((1,), bool),
        remaining=jnp.asarray([remaining], jnp.int32),
        pos=jnp.asarray([10], jnp.int32),
        token=jnp.asarray([5], jnp.int32),
    )
    win = jnp.asarray([window], jnp.int32)
    dr = jnp.asarray([draft], jnp.int32)
    new, emit, mask, adv = EP.spec_verify(
        cfg, state, win, dr, jnp.asarray([n_draft], jnp.int32),
        jnp.asarray([True]),
    )
    # the accepted stream = matched draft prefix + correction token,
    # then the slot_step simulation over it
    n_acc = 0
    for j in range(n_draft):
        if draft[j] == window[j]:
            n_acc += 1
        else:
            break
    stream = window[: n_acc + 1]
    ref_emit, ref_adv, ref_rem, ref_active, ref_tok = _simulate_plain(
        cfg, stream, remaining
    )
    got = [int(t) for t, m in zip(np.asarray(emit)[0], np.asarray(mask)[0])
           if m]
    assert got == ref_emit, (got, ref_emit)
    assert int(adv[0]) == ref_adv
    assert int(new.remaining[0]) == ref_rem
    assert bool(new.active[0]) == (ref_active and ref_rem > 0)
    assert int(new.pos[0]) == 10 + ref_adv
    if ref_active and ref_rem > 0:
        assert int(new.token[0]) == ref_tok


def test_spec_verify_inactive_and_off_rows_frozen():
    cfg = get_model_config("test-llama-tiny")
    state, _ = G.init_slots(2, cfg.vocab_size)
    state = state._replace(
        active=jnp.asarray([False, True]),
        remaining=jnp.asarray([0, 5], jnp.int32),
        pos=jnp.asarray([3, 7], jnp.int32),
    )
    win = jnp.asarray([[5, 6], [5, 6]], jnp.int32)
    dr = jnp.asarray([[5], [5]], jnp.int32)
    nd = jnp.asarray([1, 1], jnp.int32)
    # row 0: on but device-inactive; row 1: not on at all
    new, emit, mask, adv = EP.spec_verify(
        cfg, state, win, dr, nd, jnp.asarray([True, False]) & state.active
    )
    assert not np.asarray(mask).any()
    assert np.asarray(new.pos).tolist() == [3, 7]
    assert np.asarray(new.remaining).tolist() == [0, 5]


# -- engine level -------------------------------------------------------------

def test_spec_greedy_bit_identical_and_accepts(setup):
    """The acceptance bar: a speculating mixed fleet serves the exact
    greedy token streams the plain fleet serves — threaded, with warm
    prefix reuse — while verify rows actually launch on the repetitive
    stream (deterministic acceptance itself is pinned by
    test_mixed_verify_accepts_model_argmax and the draft-model leg).
    Runs both ways, plain and speculating (the back-to-back loop on
    device-derived metadata): token-identical across threads and warm
    prefix reuse."""
    cfg, params = setup
    shared = " ".join(f"ctx{j}" for j in range(24))
    prompts = MIXED_PROMPTS + [shared + " question one",
                               shared + " question two"]
    outs = {}
    for name, spec in (("plain", False), ("spec", True)):
        cont = _cont(cfg, params, spec)
        try:
            warm = [
                cont.submit(p, max_tokens=12, greedy=True, chat=False)
                for p in prompts
            ]
            wave = [None] * len(prompts)

            def run(i, c=cont, w=wave):
                w[i] = c.submit(prompts[i], max_tokens=12, greedy=True,
                                chat=False)

            ts = [
                threading.Thread(target=run, args=(i,))
                for i in range(len(prompts))
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            st = cont.stats()
        finally:
            cont.close()
        assert all(
            r is not None and r["status"] == "success" for r in warm + wave
        ), (name, warm, wave)
        outs[name] = [r["response"] for r in warm + wave]
        if spec:
            sb = st["speculative"]
            assert sb["mode"] == "ngram"
            assert sb["launches"] > 0, st
            assert sb["drafted_tokens"] > 0, st
    assert outs["spec"] == outs["plain"]


def test_mixed_verify_accepts_model_argmax():
    """Deterministic acceptance + program-level bit-identity: decode 5
    tokens with plain 1-token mixed launches, then replay the SAME
    start as ONE verify row drafting the model's own chain — the traced
    verify must emit the identical stream and leave the identical slot
    state (the chunked-vs-whole discipline, speculation edition)."""
    cfg = get_model_config("test-llama-tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    W, B, bs, MB = 16, 1, 16, 4
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    K = 4
    K1 = K + 1

    def fresh_state():
        state, sparams = G.init_slots(B, cfg.vocab_size)
        state = state._replace(
            token=jnp.asarray([7], jnp.int32),
            pos=jnp.asarray([4], jnp.int32),
            active=jnp.asarray([True]),
            remaining=jnp.asarray([6], jnp.int32),
        )
        sparams = sparams._replace(greedy=jnp.asarray([True]))
        return state, sparams

    arm = EP.idle_mixed_arm(B, cfg.vocab_size)
    key = jax.random.PRNGKey(11)

    # --- reference: 5 plain decode launches, state/pool chained
    state, sparams = fresh_state()
    pool = EP.init_pool(cfg, MB + 2, bs)
    plain = []
    for t in range(5):
        meta, tok_row, tok_pos, offs, _ = EP.build_ragged_meta(
            [(0, 4 + t, 1, EP.RAGGED_DECODE)], width=W, tile=TILE
        )
        dec_flag = np.zeros((W,), bool)
        dec_flag[offs[0]] = True
        packed, state, sparams, pool = EP.mixed_step_ragged(
            cfg, params, jnp.zeros((W,), jnp.int32), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(dec_flag), jnp.asarray(meta),
            pool, table, state, sparams, key, jnp.asarray([offs[0]],
                                                          jnp.int32), arm,
        )
        p = np.asarray(packed)
        if p[1, 0]:
            plain.append(int(p[0, 0]))
    ref_state = state

    # --- one verify row drafting the chain the model just produced
    draft = (plain + [0] * K)[:K]
    state, sparams = fresh_state()
    pool = EP.init_pool(cfg, MB + 2, bs)
    meta, tok_row, tok_pos, offs, _ = EP.build_ragged_meta(
        [(0, 4, 1 + K, EP.RAGGED_PREFILL)], width=W, tile=TILE
    )
    toks = np.zeros((W,), np.int32)
    toks[offs[0] + 1 : offs[0] + 1 + K] = draft
    dec_flag = np.zeros((W,), bool)
    dec_flag[offs[0]] = True
    spec = EP.SpecPlan(
        jnp.asarray([False]), jnp.asarray([True]),
        jnp.asarray([[offs[0] + j for j in range(K1)]], jnp.int32),
        jnp.asarray([K], jnp.int32),
    )
    packed, state, sparams, pool = EP.mixed_step_ragged(
        cfg, params, jnp.asarray(toks), jnp.asarray(tok_row),
        jnp.asarray(tok_pos), jnp.asarray(dec_flag), jnp.asarray(meta),
        pool, table, state, sparams, key, jnp.zeros((B,), jnp.int32), arm,
        spec=spec,
    )
    p = np.asarray(packed)
    em = p[5 : 5 + K1, 0]
    mk = p[5 + K1 : 5 + 2 * K1, 0].astype(bool)
    got = em[mk].tolist()
    assert got == plain, (got, plain)
    assert len(got) >= 2  # the draft actually won tokens (accept > 0)
    for field in ("pos", "token", "active", "remaining"):
        assert (
            np.asarray(getattr(state, field)).tolist()
            == np.asarray(getattr(ref_state, field)).tolist()
        ), field


def test_device_meta_derives_positions_on_device():
    """The ISSUE 15 derivation contract at the program level: a verify
    row launched with GARBAGE host-planned positions but DeviceMeta
    masks produces the bit-identical packed fetch and slot state as the
    host-exact launch — the kernel metadata and write/RoPE positions
    really come from state.pos, not the host plan."""
    cfg = get_model_config("test-llama-tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    W, B, bs, MB = 16, 1, 16, 6
    table = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    K = 4
    K1 = K + 1
    arm = EP.idle_mixed_arm(B, cfg.vocab_size)
    key = jax.random.PRNGKey(11)
    draft = [9, 17, 3, 250]
    # a real prefilled prefix so positions MATTER: a wrong q_start both
    # mis-masks the context window and mis-rotates RoPE relative to it
    prefix = [(31 + 13 * j) % cfg.vocab_size for j in range(64)]

    def fresh():
        pool = EP.init_pool(cfg, MB + 2, bs)
        for c in range(2):
            meta, tok_row, tok_pos, _, _ = EP.build_ragged_meta(
                [(0, c * 32, 32, EP.RAGGED_PREFILL)], width=32, tile=TILE
            )
            pool = EP.extend_ragged_paged(
                cfg, params,
                jnp.asarray(prefix[c * 32 : (c + 1) * 32], jnp.int32),
                jnp.asarray(tok_row), jnp.asarray(tok_pos),
                jnp.asarray(meta), pool, table,
            )
        state, sparams = G.init_slots(B, cfg.vocab_size)
        state = state._replace(
            token=jnp.asarray([prefix[-1]], jnp.int32),
            pos=jnp.asarray([63], jnp.int32),
            active=jnp.asarray([True]),
            remaining=jnp.asarray([6], jnp.int32),
        )
        sparams = sparams._replace(greedy=jnp.asarray([True]))
        return state, sparams, pool

    def run(start, dev):
        entries = [(0, start, 1 + K, EP.RAGGED_PREFILL)]
        meta, tok_row, tok_pos, offs, _ = EP.build_ragged_meta(
            entries, width=W, tile=TILE
        )
        toks = np.zeros((W,), np.int32)
        toks[offs[0] + 1 : offs[0] + 1 + K] = draft
        dec_flag = np.zeros((W,), bool)
        dec_flag[offs[0]] = True
        spec = EP.SpecPlan(
            jnp.asarray([False]), jnp.asarray([True]),
            jnp.asarray([[offs[0] + j for j in range(K1)]], jnp.int32),
            jnp.asarray([K], jnp.int32),
        )
        dev_op = None
        if dev:
            t_on, t_off, k_on, k_off = EP.build_device_meta(
                entries, offs, 1, width=W, tile=TILE
            )
            dev_op = EP.DeviceMeta(
                jnp.asarray(t_on), jnp.asarray(t_off),
                jnp.asarray(k_on), jnp.asarray(k_off),
            )
        state, sparams, pool = fresh()
        packed, state, _, _ = EP.mixed_step_ragged(
            cfg, params, jnp.asarray(toks), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.asarray(dec_flag), jnp.asarray(meta),
            pool, table, state, sparams, key, jnp.zeros((B,), jnp.int32),
            arm, spec=spec, spec_toks=None, dev=dev_op,
        )
        return np.asarray(packed), state

    exact, state_e = run(start=63, dev=False)  # host-exact baseline
    derived, state_d = run(start=7, dev=True)  # garbage host plan
    assert exact.tolist() == derived.tolist()
    for field in ("pos", "token", "active", "remaining"):
        assert (
            np.asarray(getattr(state_d, field)).tolist()
            == np.asarray(getattr(state_e, field)).tolist()
        ), field
    # and the garbage plan WITHOUT derivation really is garbage (the
    # test would otherwise prove nothing)
    junk, _ = run(start=7, dev=False)
    assert junk.tolist() != exact.tolist()


def test_spec_launches_every_step_back_to_back(setup):
    """No freeze (ISSUE 15 acceptance): a speculating slot submits a
    verify row while its previous one is still unfetched
    (pipelined_launches > 0), and serves the plain fleet's greedy stream
    token for token."""
    cfg, params = setup
    outs, stats = {}, {}
    for spec in (True, False):
        cont = _cont(cfg, params, spec)
        try:
            r = cont.submit(REPEAT_PROMPT, max_tokens=24, greedy=True,
                            chat=False)
            st = cont.stats()
        finally:
            cont.close()
        assert r["status"] == "success"
        outs[spec] = r["response"]
        stats[spec] = st.get("speculative")
    assert outs[True] == outs[False]
    sb = stats[True]
    assert stats[False] is None  # spec_draft_len 0: no machinery at all
    assert sb["launches"] > 0
    # every-step verify: back-to-back rows while earlier ones are unfetched
    assert sb["pipelined_launches"] > 0, sb


def test_spec_metrics_and_envelope(setup):
    cfg, params = setup
    cont = _cont(cfg, params, True)
    try:
        r = cont.submit(REPEAT_PROMPT, max_tokens=16, greedy=True,
                        chat=False, speculative=True)
        snap = cont.engine.metrics.snapshot()
    finally:
        cont.close()
    assert r["status"] == "success"
    assert r.get("continuous") is True  # served in-fleet, not solo
    assert r.get("speculative") is True
    assert r.get("spec_path") == "fleet"
    assert r.get("spec_drafted", 0) >= r.get("spec_accepted", 0) >= 0
    assert r["spec_drafted"] > 0
    total = sum(
        s["value"]
        for s in snap.get("dli_spec_drafted_tokens_total", {}).get(
            "series", []
        )
    )
    assert total > 0
    assert "dli_spec_launches_total" in snap
    assert "dli_spec_tokens_per_launch" in snap
    # adaptive drafting observability (ISSUE 15): planned K histogram
    # populated per verify row, acceptance-EWMA gauge present
    k_hist = snap.get("dli_spec_draft_len", {}).get("series", [])
    assert sum(s["count"] for s in k_hist) > 0, snap.get(
        "dli_spec_draft_len"
    )
    assert "dli_spec_accept_ewma" in snap


def test_speculative_request_runs_in_fleet_even_when_fleet_default_off(setup):
    """Satellite: the solo fallback for speculative requests is lifted —
    a greedy "speculative": true request on a spec-capable fleet decodes
    in-fleet (and matches the plain fleet's greedy stream); seeded
    requests keep the solo contract."""
    cfg, params = setup
    cont = _cont(cfg, params, False,
                 engine_cfg={"spec_draft_len": 4, "spec_decode": False})
    try:
        plain = cont.submit(REPEAT_PROMPT, max_tokens=10, greedy=True,
                            chat=False)
        spec = cont.submit(REPEAT_PROMPT, max_tokens=10, greedy=True,
                           chat=False, speculative=True)
        seeded = cont.submit(REPEAT_PROMPT, max_tokens=10, greedy=True,
                             chat=False, speculative=True, seed=7)
    finally:
        cont.close()
    assert spec.get("continuous") is True
    assert spec["spec_path"] == "fleet"
    assert spec["response"] == plain["response"]
    # seeded/debug contracts still go solo (per-request RNG stream)
    assert "continuous" not in seeded
    assert seeded.get("spec_path") == "solo"


def test_spec_disables_under_tpot_pressure_engine(setup):
    """Engine leg of the throttle: with observed TPOT over every active
    class target, the fleet plans no verify rows at all."""
    cfg, params = setup
    cont = _cont(cfg, params, True)
    try:
        # poison the feedback EWMA before any traffic: decode pressure
        for name in cont._slo:
            cont._sched.observe(name, 0.01, 99.0)
        r = cont.submit(REPEAT_PROMPT, max_tokens=12, greedy=True,
                        chat=False)
        st = cont.stats()
    finally:
        cont.close()
    assert r["status"] == "success"
    assert st["speculative"]["launches"] == 0


def test_non_greedy_request_never_speculates_but_stays_in_fleet(setup):
    cfg, params = setup
    cont = _cont(cfg, params, True)
    try:
        r = cont.submit(REPEAT_PROMPT, max_tokens=8, temperature=0.9,
                        chat=False, speculative=True)
        st = cont.stats()
    finally:
        cont.close()
    assert r["status"] == "success"
    assert r.get("continuous") is True
    assert st["speculative"]["launches"] == 0


def test_spec_with_long_prompt_interleaving(setup):
    """Verify rows and prefill chunks share launches: a long admission
    mid-flight neither stalls nor corrupts a speculating decoder."""
    cfg, params = setup
    long_prompt = "y " * 150
    outs = {}
    for spec in (False, True):
        cont = _cont(cfg, params, spec)
        try:
            cont.submit(REPEAT_PROMPT, max_tokens=4, greedy=True,
                        chat=False)  # warm
            res = [None, None]

            def d(c=cont, r=res):
                r[0] = c.submit(REPEAT_PROMPT, max_tokens=20, greedy=True,
                                chat=False)

            def l(c=cont, r=res):
                time.sleep(0.05)
                r[1] = c.submit(long_prompt, max_tokens=6, greedy=True,
                                chat=False)

            ts = [threading.Thread(target=d), threading.Thread(target=l)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
        finally:
            cont.close()
        assert all(r is not None and r["status"] == "success" for r in res)
        outs[spec] = [r["response"] for r in res]
    assert outs[True] == outs[False]


# -- chaos: crash / preemption mid-spec-cycle --------------------------------

@pytest.mark.chaos
def test_crash_mid_spec_cycle_salvages_bit_identical(setup):
    """A scheduler crash while verify rows are in flight salvages every
    request with greedy output bit-identical to a fault-free plain run —
    unfetched verify emissions drop exactly like unfetched chunks, and
    the back-to-back pending verify windows die with the fleet."""
    cfg, params = setup
    prompts = [REPEAT_PROMPT, "the quick brown fox"]

    def serve(spec_decode, rules):
        faults.disarm()
        cont = _cont(cfg, params, spec_decode,
                     engine_cfg={"prefix_cache_entries": 0})
        try:
            if rules:
                faults.arm(rules)
            out = {
                p: cont.submit(p, max_tokens=12, greedy=True, chat=False)
                for p in prompts
            }
            return out, cont.restarts_total, cont.stats()
        finally:
            faults.disarm()
            cont.close()

    clean, _, _ = serve(False, None)
    assert all(r["status"] == "success" for r in clean.values())
    # crash a later decode launch: by then the repetitive stream has
    # fetched history and speculates, so the crash lands mid-spec-cycle
    crashed, restarts, st = serve(
        True, [faults.FaultRule("decode_launch", "transient", on_call=4)],
    )
    assert restarts >= 1
    assert st["speculative"]["launches"] > 0
    for p in prompts:
        assert crashed[p]["status"] == "success", crashed[p]
        assert crashed[p]["response"] == clean[p]["response"], p


@pytest.mark.chaos
def test_preemption_mid_spec_stays_bit_identical(setup):
    """A pool-pressure preemption landing while the victim speculates
    resumes bit-identical: in-flight verify emissions drop via the
    drop_seq barrier and regenerate after resume."""
    cfg, params = setup

    def serve(spec):
        cont = _cont(
            cfg, params, spec,
            kv_pool_blocks=24, kv_block_size=16, n_slots=2,
            slot_max_seq=256,
            engine_cfg={
                "prefix_cache_entries": 0, "preempt_policy": "recompute",
                "kv_shadow": False, "kv_fabric": False,
            },
        )
        try:
            cont.submit("warm", max_tokens=2, greedy=True, chat=False)
            out = [None, None]
            started = threading.Event()

            def d(c=cont, r=out):
                started.set()
                r[0] = c.submit(REPEAT_PROMPT, max_tokens=24, greedy=True,
                                chat=False)

            def l(c=cont, r=out):
                started.wait(10)
                # wait until the decoder actually DECODES (past prefill)
                # so the pressure ladder can pick it as a victim
                for _ in range(200):
                    st = cont.stats()
                    if (
                        st["occupied"] >= 1
                        and st.get("scheduler", {}).get("prefilling", 0)
                        == 0
                    ):
                        break
                    time.sleep(0.02)
                r[1] = c.submit("z " * 120, max_tokens=4, greedy=True,
                                chat=False)

            ts = [threading.Thread(target=d), threading.Thread(target=l)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            return out, cont.preempted_total
        finally:
            cont.close()

    plain, pre_plain = serve(False)
    spec, pre_spec = serve(True)
    assert all(r is not None and r["status"] == "success" for r in plain)
    assert all(r is not None and r["status"] == "success" for r in spec)
    # the eviction really landed (otherwise this test pins nothing)
    assert pre_spec > 0 and pre_plain > 0, (pre_spec, pre_plain)
    assert [r["response"] for r in spec] == [r["response"] for r in plain]


# -- draft-model flavor -------------------------------------------------------

def test_draft_model_fleet_accepts_everything_with_identical_draft(setup):
    """cfg-gated draft model sharing the pool: with the draft == the
    target, every draft matches the target's argmax — acceptance is
    total, output identical to the plain fleet."""
    cfg, params = setup
    eng = InferenceEngine(
        cfg, params=params,
        engine_cfg=EngineConfig(
            prefix_cache_entries=0, chunked_prefill=True,
            step_token_budget=64, prefill_buckets=(64, 128, 256),
            spec_decode=True, spec_draft_len=3,
            spec_draft_model="test-llama-tiny",
        ),
    )
    eng.set_draft(cfg, params)  # attached draft wins over the named cfg
    cont = ContinuousEngine(
        eng, n_slots=2, chunk_steps=8, slot_max_seq=512,
        kv_pool_blocks=120, kv_block_size=16, restart_backoff_s=0.01,
    )
    try:
        r = cont.submit("the quick brown fox jumps", max_tokens=12,
                        greedy=True, chat=False)
        st = cont.stats()
    finally:
        cont.close()
    assert r["status"] == "success"
    sb = st["speculative"]
    assert sb["mode"] == "draft_model"
    assert sb["launches"] > 0
    # a perfect draft accepts every drafted token it has budget for
    assert sb["accepted_tokens"] > 0
    # bit-identity against the plain fleet
    cont2 = _cont(cfg, params, False)
    try:
        r2 = cont2.submit("the quick brown fox jumps", max_tokens=12,
                          greedy=True, chat=False)
    finally:
        cont2.close()
    assert r["response"] == r2["response"]


# -- pp shard_map twin --------------------------------------------------------

@pytest.mark.parametrize("live_width", [None, 24], ids=["tiles", "compact"])
def test_pp_spec_mixed_step_token_identical(setup, eight_devices, live_width):
    """The pipeline's spec-mixed program produces the identical packed
    fetch / slot state as the single-device program on the same
    operands — pp verify rows cannot drift. With a `live_width` under the
    launch's width both run the live tokens packed on that axis
    (engine/scheduler.live_width): the same tokens again."""
    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.analysis.hlo import _spec_mixed_args
    from distributed_llm_inference_tpu.parallel.mesh import build_mesh
    from distributed_llm_inference_tpu.parallel.pipeline import (
        PipelineBackend,
    )

    cfg = get_model_config("test-llama-tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    class _Eng:
        pass

    eng = _Eng()
    eng.cfg = cfg
    # NOTE: a class body cannot close over these function locals (plain
    # attribute assignment instead — `class _B: cfg = cfg` NameErrors)
    backend = _Eng()
    backend.cfg = cfg
    backend.params = params
    eng.backend = backend
    mesh = build_mesh(MeshConfig(dp=1, pp=2, tp=1), eight_devices)
    (acfg, aparams, toks, tok_row, tok_pos, dec_flag, meta, pool,
     table, state, sparams, key, dec_idx, arm, spec, spec_toks, dev) = (
        _spec_mixed_args(eng, n_spec=1, n_draft=3, chunk=9)
    )
    cpu_cfg = acfg.replace(attn_impl="xla")
    packed = {}
    for width in (None, live_width):  # (the tile layout is the reference)
        kw = {} if width is None else {"live_width": width}
        assert width is None or int(np.sum(np.asarray(tok_row) >= 0)) \
            <= width < tok_row.shape[0]
        packed[width], state_s, _, _ = EP.mixed_step_ragged(
            cpu_cfg, params, toks, tok_row, tok_pos, dec_flag, meta,
            EP.init_pool(cpu_cfg, 10, 16), table, state, sparams, key,
            dec_idx, arm, spec=spec, spec_toks=spec_toks, dev=dev, **kw,
        )
    packed_s = packed[live_width]
    assert np.asarray(packed_s).tolist() == np.asarray(packed[None]).tolist()
    pb = PipelineBackend(cpu_cfg, params, mesh)
    pool_pp = pb.init_paged_pool(10, 16)
    packed_p, state_p, _, _ = pb.mixed_step_ragged(
        toks, tok_row, tok_pos, dec_flag, meta, pool_pp, table,
        state, sparams, key, dec_idx, arm, spec=spec,
        spec_toks=spec_toks, dev=dev, **kw,
    )
    assert np.asarray(packed_s).tolist() == np.asarray(packed_p).tolist()
    assert (
        np.asarray(state_s.pos).tolist()
        == np.asarray(state_p.pos).tolist()
    )
    assert (
        np.asarray(state_s.token).tolist()
        == np.asarray(state_p.token).tolist()
    )
