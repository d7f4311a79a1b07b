"""Generation by diffusion over blocks, through the served engine (ISSUE
32): `engine/continuous.py` over the paged pool at `test-sdar-tiny`, seeded
random weights. Every token the fleet delivers is held against the plain
reference's LOGITS at the denoise state that reveals it (its margin below
the reference's best, tests/sdar_util.py), never against tokens alone:
whole and chunked prefill, a prefix hit, a prompt whose whole blocks are
all mapped, prompts of all four remainders mod 4, denoise_steps 1, 2 and
4, budgets that end inside a block, rows at different phases in one
launch, interpreted Pallas and XLA attention, float32 and bfloat16; and
the open block's invariants: nothing uncommitted reaches the prefix index,
another row, a preemption or `_release_ended`, and the host's position
model agrees with the device after every launch. ISSUE 33: a clean block's
commit rides the next block's first denoise forward, so the position model
(`_blk_plan`, `_blk_at`) is held against a replay of the device's own state,
and a closed-loop rehearsal against the tokens the three-forward form served
(tests/data/sdar_three_forward_tokens.json, recorded from the parent).
"""

import functools
import json
import os
import types

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, create_engine
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.models.registry import get_model_config

from sdar_util import margins, ref_logits

SEED, BS, B = 3, 16, 4


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
    """A served fleet that checks, after EVERY launch, that the host's
    position model and the device agree on every live row's length."""

    def __init__(self, impl="xla", dtype="float32", steps=2, budget=16, slots=2,
                 pool=24, chunk=3, **kw):
        self.steps, self.dtype = steps, dtype
        self.eng = create_engine(
            "test-sdar-tiny", seed=SEED, attn_impl=impl, dtype=dtype,
            engine_cfg=EngineConfig(prefix_cache_entries=8, denoise_steps=steps,
                                    step_token_budget=budget))
        self.eng.tokenizer = WordTok()
        self.ce = ContinuousEngine(
            self.eng, n_slots=slots, chunk_steps=chunk, kv_pool_blocks=pool,
            kv_block_size=BS, kv_shadow=False, slot_max_seq=96, **kw)
        self.cfg = self.eng.cfg
        self.disagreements, self.launches = [], 0
        for name in ("_launch_mixed", "_launch_chunk"):
            setattr(self.ce, name, self._checked(getattr(self.ce, name)))

    def _checked(self, launch):
        def run(*a, **kw):
            out = launch(*a, **kw)
            if out is not None:
                self._compare()
            return out
        return run

    def _compare(self):
        ce = self.ce
        self.launches += 1
        pos = np.asarray(ce.state.pos)
        active = np.asarray(ce.state.active)
        done = np.minimum(ce._host_pos, ce._host_end)
        length = ce._blk_at(done)[0]
        for b, req in enumerate(ce._assignment):
            if req is None or b in ce._prefilling:
                continue
            want = (int(length[b]), bool(ce._host_pos[b] < ce._host_end[b]))
            if (int(pos[b]), bool(active[b])) != want:
                self.disagreements.append((b, int(pos[b]), bool(active[b]), want))

    def ask_all(self, asks):
        """asks: [(ids, max_tokens, extra kwargs)] sent together. Returns
        the envelopes with `ids` (the generated ids) added."""
        out = [None] * len(asks)

        def one(i, ids, mt, kw):
            out[i] = self.ce.submit(words(ids), max_tokens=mt, greedy=True,
                                    chat=False, **kw)

        ts = [threading.Thread(target=one, args=(i, *a)) for i, a in enumerate(asks)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(300)
        for r in out:
            assert r is not None and r.get("status") == "success", r
            r["ids"] = WordTok().encode(r["response"])
        return out

    def margins(self, prompt, res, steps=None):
        lg = ref_logits(self.cfg, SEED, steps or self.steps, prompt + res["ids"],
                        len(prompt), jnp.dtype(self.dtype))
        return margins(lg, res["ids"])

    def close(self):
        self.ce.close()
        assert not self.disagreements, self.disagreements[:5]


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
        f.close()
    _FLEETS.clear()


def _series(eng, name):
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in eng.metrics.snapshot().get(name, {}).get("series", [])}


# prompts of all four remainders mod 4, one shorter than a block, one of
# several prefill chunks (45 tokens at a 16-token step budget); budgets that
# end inside a block (14, 9, 10) and on its edge (12, 8); six rows for two
# slots, so rows at different phases share launches and slots are re-let
ASKS = [(20, 14), (21, 9), (22, 12), (23, 10), (3, 6), (45, 8)]


def _served_is_the_references_choice(f, tol):
    asks = [(prompt_ids(n), mt, {}) for n, mt in ASKS]
    res = f.ask_all(asks)
    worst = []
    for (ids, mt, _), r in zip(asks, res):
        assert len(r["ids"]) == mt == r["tokens_generated"]  # exactly max_tokens
        assert r["prompt_tokens"] == len(ids) and r["finish_reason"] == "length"
        worst.append(f.margins(ids, r))
    m = np.concatenate(worst)
    assert m.max() <= tol, (m.max(), (m > 0).mean())
    assert not f.disagreements, f.disagreements[:5]
    return m


@pytest.mark.parametrize("impl,steps", [("xla", 1), ("xla", 2), ("xla", 4), ("pallas", 2)])
def test_served_tokens_are_the_references_choice_at_every_denoise_state(impl, steps):
    f = fleet(impl=impl, steps=steps)
    _served_is_the_references_choice(f, 1e-4)
    kinds = _series(f.eng, "dli_diffusion_row_forwards_total")
    delivered = _series(f.eng, "dli_diffusion_tokens_total")[()]
    assert delivered >= sum(mt for _, mt in ASKS)
    # every row-forward reveals; none only writes a clean block, and the
    # commits ride: one a block but a request's last
    assert kinds[(("kind", "denoise"),)] > 0 and kinds[(("kind", "commit"),)] == 0
    assert _series(f.eng, "dli_diffusion_fused_commits_total")[()] >= sum(
        -(-(n % B + mt) // B) - 1 for n, mt in ASKS)
    # every row ended by its budget: the position model released every slot
    assert _series(f.eng, "dli_slot_release_total").get((("by", "fetch"),), 0) == 0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_served_in_bfloat16_stays_near_the_references_choice(impl):
    """bfloat16 against the float32 reference on the same (bfloat16)
    weights: a rounding flips a near-tie (a router's, a token's) now and
    then, a wrong rule everything (tests/test_block_diffusion.py's mutants
    read over 0.2 in float32)."""
    f = fleet(impl=impl, dtype="bfloat16")
    m = _served_is_the_references_choice(f, 4.0)
    assert m.mean() < 0.25 and (m > 0).mean() < 0.45, (m.mean(), (m > 0).mean())


@pytest.mark.parametrize("budget", [256, None], ids=["256", "derived"])
def test_chunked_prefill_equals_whole_prefill(budget):
    """At an explicit 256 and at the width derived from the model (a routed
    bank of five experts to one computed: 512, engine/scheduler.step_width)."""
    asks = [(prompt_ids(n), mt, {}) for n, mt in ASKS]
    a = fleet(impl="xla", steps=2).ask_all(asks)
    whole = fleet(impl="xla", steps=2, budget=budget)
    assert whole.ce.stats()["scheduler"]["step_width"] == (budget or 512)
    b = whole.ask_all(asks)
    assert [r["ids"] for r in a] == [r["ids"] for r in b]
    m = np.concatenate([whole.margins(ids, r) for (ids, _, _), r in zip(asks, b)])
    assert m.max() <= 1e-4


def test_prefix_hit_and_a_prompt_whose_whole_blocks_are_all_mapped():
    f = fleet(impl="xla", steps=2)
    doc = prompt_ids(34, salt=7)  # two pool blocks of 16 and a remainder
    first = f.ask_all([(doc, 10, {})])[0]
    assert "prefix_cached_tokens" not in first
    longer = doc + prompt_ids(9, salt=8)
    # the same 33 tokens again: its whole blocks (32) are all mapped, so no
    # chunk is left to land and the slot is armed by a launch that carries
    # none; and a longer prompt on the shared head
    again, more = f.ask_all([(doc[:33], 7, {})])[0], f.ask_all([(longer, 9, {})])[0]
    assert again["prefix_cached_tokens"] == 32 == more["prefix_cached_tokens"]
    for ids, r in ((doc, first), (doc[:33], again), (longer, more)):
        assert f.margins(ids, r).max() <= 1e-4
    # only what the PROMPT's whole pool blocks held was registered: positions
    # 32.. were committed by generation, block by block, and the index holds
    # none of them
    p0, _, _ = f.ce._bpx.lookup(doc + first["ids"] + [5] * 20)
    assert p0 == 32
    short = prompt_ids(30, salt=9)  # 28 committed by prefill, 16 registered
    r = f.ask_all([(short, 10, {})])[0]
    assert f.ce._bpx.lookup(short + r["ids"] + [5] * 8)[0] == 16
    other = f.ask_all([(short[:29] + [7, 8, 9], 6, {})])[0]  # shares the block
    assert other["prefix_cached_tokens"] == 16
    assert f.margins(short[:29] + [7, 8, 9], other).max() <= 1e-4


def test_denoise_steps_is_a_request_field_with_a_server_default():
    f = fleet(impl="xla", steps=2)
    ids = prompt_ids(22, salt=3)
    one, four = f.ask_all([(ids, 9, {"denoise_steps": 1}),
                           (ids, 9, {"denoise_steps": 4})])
    assert f.margins(ids, one, steps=1).max() <= 1e-4
    assert f.margins(ids, four, steps=4).max() <= 1e-4
    bad = f.ce.submit(words(ids), max_tokens=4, greedy=True, chat=False,
                      denoise_steps=3)
    assert bad["error_type"] == "invalid_request" and "divide" in bad["error"]
    for kw in ({"repetition_penalty": 1.3}, {"seed": 5}, {"logprobs": True}):
        bad = f.ce.submit(words(ids), max_tokens=4, chat=False, **kw)
        assert bad["error_type"] == "invalid_request", kw
    events = list(f.ce.stream(words(ids), max_tokens=4, chat=False,
                              frequency_penalty=0.5))
    assert events[-1]["done"] and events[-1]["error_type"] == "invalid_request"


def test_a_stream_delivers_a_block_when_it_comes_clean():
    f = fleet(impl="xla", steps=2)
    ids = prompt_ids(21, salt=4)  # one prompt token heads the first block
    events = list(f.ce.stream(words(ids), max_tokens=13, greedy=True, chat=False))
    final = events[-1]
    assert final["done"] and final["tokens_generated"] == 13
    counts = [e["tokens_so_far"] for e in events[:-1]]
    assert counts and counts[-1] == 13
    # whole blocks at absolute positions: 3, 7, 11, then the budget's end
    assert all((len(ids) + c) % B == 0 or c == 13 for c in counts), counts
    text = "".join(e["delta"] for e in events[:-1])
    assert WordTok().encode(text) == WordTok().encode(final["response"])


def test_sampled_rows_never_emit_the_mask_id():
    f = fleet(impl="xla", steps=2)
    ids = prompt_ids(20, salt=5)
    # the engine's key starts from the clock; at this temperature one draw in
    # 255 is the stop token, and a row that ends there ends where only the
    # fetch can see it (3 keys of 80 leave the fleet's check a disagreement,
    # on the parent of PR 46 too). A pinned key draws all 24 tokens.
    f.ce._key = jax.random.PRNGKey(0)
    r = f.ce.submit(words(ids), max_tokens=24, temperature=5.0, top_k=0,
                    top_p=1.0, chat=False)
    out = WordTok().encode(r["response"])
    assert r["status"] == "success" and f.cfg.mask_token_id not in out
    assert r["tokens_generated"] == 24


def test_a_preempted_row_resumes_on_committed_blocks_only():
    """A pool that cannot hold both rows: the second admission evicts the
    first mid-decode. What the victim keeps is what it delivered, whole
    committed blocks; its open block is recomputed from masks after the
    resume, and both answers stay the reference's choice."""
    f = Fleet(impl="xla", steps=2, pool=7, chunk=2)  # 6 usable blocks
    try:
        a_ids, b_ids = prompt_ids(33, salt=11), prompt_ids(30, salt=12)
        out = {}

        def run(tag, ids, mt):
            out[tag] = f.ce.submit(words(ids), max_tokens=mt, greedy=True, chat=False)

        ta = threading.Thread(target=run, args=("a", a_ids, 60))  # all 6 blocks
        ta.start()
        import time
        t0 = time.time()
        # B arrives once A has committed a block (a prefilling row is no victim)
        while time.time() - t0 < 120 and not any(
                r is not None and r.tokens for r in f.ce._assignment):
            time.sleep(0.002)
        tb = threading.Thread(target=run, args=("b", b_ids, 12))  # 3 blocks
        tb.start()
        ta.join(300), tb.join(300)
        a, b = out["a"], out["b"]
        assert a["status"] == b["status"] == "success"
        assert a.get("preempted", 0) >= 1 and a["tokens_generated"] == 60
        for ids, r in ((a_ids, a), (b_ids, b)):
            r["ids"] = WordTok().encode(r["response"])
            assert f.margins(ids, r).max() <= 1e-4
        st = f.ce.stats()["paged"]
        assert st["free_blocks"] + st["cached_blocks"] == st["pool_blocks"] - 1
    finally:
        f.close()


def test_launch_records_count_forwards_and_kv_once_a_forward():
    f = Fleet(impl="pallas", steps=2, slots=1, chunk=3)
    try:
        recs = []
        orig = f.ce._launch_record

        def keep(*a, **kw):
            recs.append(orig(*a, **kw))
            return recs[-1]

        f.ce._launch_record = keep
        ids = prompt_ids(16, salt=13)
        f.ask_all([(ids, 8, {})])
        dec = [r for r in recs if r["decode_rows"]]
        # 8 tokens = 2 blocks x 2 denoise row-forwards; the first block's
        # commit rides the second's first forward, the last block has none
        assert sum(r["denoise_rows"] for r in dec) == 4
        assert sum(r["commit_rows"] for r in dec) == 0
        assert sum(r["fused_rows"] for r in dec) == 1
        assert sum(r["revealed_tokens"] for r in dec) == 8
        assert all(r["forwards"] == r["steps"] for r in dec)
        # a forward reads the row's whole cache plus its open block, once
        # (the owed block's positions are part of that cache): two forwards
        # at length 16, two at 20
        assert sum(r["kv_tokens"] for r in dec) == 2 * 20 + 2 * 24
        # and the kernel walks whole 16-token pool blocks: two of them
        assert sum(r["kv_grid_tokens"] for r in dec) == 4 * 32
    finally:
        f.close()


# ---- ISSUE 33: the position model in forwards, the fused commit ---------------

@functools.lru_cache(None)
def _step():
    return jax.jit(functools.partial(P.diffusion_step, get_model_config("test-sdar-tiny")))


@pytest.mark.parametrize("budget", [1, 4, 6, 13])
@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("head", [0, 1, 2, 3])
def test_position_model_against_a_replay_of_the_devices_state(head, steps, budget):
    """`_blk_plan` / `_blk_at` say, without a fetch, what the device's own
    state says forward by forward: the open block's position, whether the
    forward carries an owed block, the masks it reveals, and the row's last
    forward (`_host_end`)."""
    cfg, base = get_model_config("test-sdar-tiny"), 8
    host = types.SimpleNamespace(
        _blk=B, _blk_base=np.array([base]), _blk_first=np.ones(1, np.int64),
        _blk_later=np.ones(1, np.int64), _blk_skip=np.zeros(1, np.int64),
        _host_pos=np.zeros(1, np.int64), _host_end=np.zeros(1, np.int64))
    ContinuousEngine._blk_plan(host, 0, head, B // steps, budget)
    state, sparams = G.init_slots(1, cfg.vocab_size)
    state = state._replace(pos=jnp.asarray([base], jnp.int32), active=jnp.asarray([True]),
                           remaining=jnp.asarray([budget], jnp.int32))
    mk = cfg.mask_token_id
    diff = P.init_diffusion(cfg, 1)._replace(
        open=jnp.asarray([[7] * head + [mk] * (B - head)], jnp.int32),
        skip=jnp.asarray([head], jnp.int32), reveal=jnp.asarray([B // steps], jnp.int32))
    logits = jnp.zeros((1, B, cfg.vocab_size)).at[:, :, 17].set(1.0)
    f = delivered = 0
    while bool(state.active[0]):
        at, owed, shown = (a[0] for a in ContinuousEngine._blk_at(host, np.array([f])))
        assert (int(state.pos[0]), bool(diff.owe[0])) == (at, owed), f
        masks = int((np.asarray(diff.open) == mk).sum())
        was = int(state.pos[0])
        state, diff, _, ok = _step()(state, sparams, diff, logits, jax.random.PRNGKey(f))
        left = 0 if int(state.pos[0]) > was else int((np.asarray(diff.open) == mk).sum())
        assert masks - left == shown, f
        delivered += int(np.asarray(ok).sum())
        f += 1
    assert f == host._host_end[0] and delivered == budget
    # past the last forward the model's length is the last block's end, and
    # the device owes nothing: the row's last block is never committed
    assert ContinuousEngine._blk_at(host, host._host_end)[0][0] == int(state.pos[0])
    assert not bool(diff.owe[0])


with open(os.path.join(os.path.dirname(__file__), "data",
                       "sdar_three_forward_tokens.json")) as _f:
    THREE_FORWARD = json.load(_f)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_closed_loop_serves_the_three_forward_forms_tokens_in_fewer_forwards(steps):
    """Eight requests for two slots, all queued before the worker sees the
    first (under the engine's own condition, on which it sleeps): the queue
    is never empty while a slot is let again, BY CONSTRUCTION and not by
    how fast client threads come back under a loaded machine, and the whole
    launch sequence follows from the queue alone. So slots are re-let by
    the position model alone; no launch carries a row past its last
    forward; the commits ride (blocks - 1 a request); and the tokens are
    what the parent's three-forward form served."""
    from distributed_llm_inference_tpu.engine.continuous import _Request

    f = Fleet(impl="xla", steps=steps)
    try:
        recs, orig = [], f.ce._launch_record

        def keep(*a, **kw):
            recs.append(orig(*a, **kw))
            return recs[-1]

        f.ce._launch_record = keep
        asks = [(prompt_ids(n, salt=THREE_FORWARD["salt"]), mt)
                for n, mt in THREE_FORWARD["asks"]]
        reqs = [_Request(words(ids), dict(max_tokens=mt, greedy=True, chat=False))
                for ids, mt in asks]
        with f.ce._cv:
            for req in reqs:
                assert f.ce._enqueue(req) is None
        for req in reqs:
            assert req.done.wait(600) and req.result.get("status") == "success", req.result
        got = [WordTok().encode(req.result["response"]) for req in reqs]
        assert got == THREE_FORWARD["tokens"][str(steps)]
        assert not f.disagreements, f.disagreements[:5]
        released = _series(f.eng, "dli_slot_release_total")
        assert released == {(("by", "model"),): len(asks)}, released
        blocks = [-(-(len(ids) % B + mt) // B) for ids, mt in asks]
        forwards = [-(-(B - len(ids) % B) // (B // steps)) + (n - 1) * steps
                    for (ids, _), n in zip(asks, blocks)]
        kinds = _series(f.eng, "dli_diffusion_row_forwards_total")
        assert kinds[(("kind", "denoise"),)] == sum(forwards)  # three-forward: + sum(blocks)
        assert kinds[(("kind", "commit"),)] == 0
        fused = _series(f.eng, "dli_diffusion_fused_commits_total")[()]
        assert fused == sum(blocks) - len(asks) == sum(r["fused_rows"] for r in recs)
        # a mixed launch holds an entry for every decode row it is handed:
        # each is alive by the position model (none past its `_host_end`)
        mixed = [r for r in recs if r["phase"] == "mixed"]
        assert mixed and all(r["denoise_rows"] == r["decode_rows"] for r in mixed)
    finally:
        f.close()
