"""Generation by diffusion over blocks (ISSUE 32: SDAR-30B-A3B-Chat), below
the engine: the block mask of both paged kernels and of the XLA forms
against a dense mask, the routed layer against the every-expert form, the
plain reference's row convention against a step-by-step replay, the step
program (chunked block-masked prefill into the pool, denoise forwards,
commits) against the reference's LOGITS at every denoise state, and what
each rule is for: the same comparison fails once the block mask, the
commit, the read at the position itself or the mask id's suppression is
taken out.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import get_model_config
from distributed_llm_inference_tpu.ops import attention as A
from distributed_llm_inference_tpu.ops.paged_attention import (
    paged_flash_attend, ragged_paged_attend,
)

from sdar_util import REF, margins, ref_config, ref_logits, ref_params

SEED = 3
BS = 16  # pool block size of these tests


def _cfg(impl="xla", **kw):
    return get_model_config("test-sdar-tiny", attn_impl=impl, **kw)


# ---- the mask ----------------------------------------------------------------

def test_block_frontier_is_the_published_rule():
    t = np.arange(23)
    for B in (1, 4, 8):
        end = np.asarray(A.block_frontier(jnp.asarray(t), B))
        for s in range(40):
            assert ((s <= end) == (s < (t // B + 1) * B)).all()
    assert (np.asarray(A.block_frontier(jnp.asarray(t), 0)) == t).all()
    m = np.asarray(A.causal_mask(jnp.int32(4), 4, 12, block=4))
    assert m[:, :8].all() and not m[:, 8:].any()


def _dense(q, k, v, q_pos, B):
    """q [W, H, Dh] at positions q_pos against k / v [S, KV, Dh] under the
    block mask, plainly."""
    W, H, Dh = q.shape
    S, KV, _ = k.shape
    kr, vr = np.repeat(k, H // KV, axis=1), np.repeat(v, H // KV, axis=1)
    s = np.einsum("whd,shd->hws", q, kr) * Dh ** -0.5
    seen = np.arange(S)[None, :] < ((q_pos // B + 1) * B)[:, None]
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hws,shd->whd", p, vr)


@pytest.mark.parametrize("kernel", ["ragged", "decode", "xla"])
def test_block_mask_of_the_paged_kernels_against_a_dense_mask(kernel):
    rng = np.random.default_rng(0)
    H, KV, Dh, B, S = 4, 2, 128, 4, 2 * BS
    k = rng.standard_normal((S, KV, Dh)).astype(np.float32)
    v = rng.standard_normal((S, KV, Dh)).astype(np.float32)
    table = np.array([[2, 1]], np.int32)  # logical block j -> physical

    def pool_of(a):  # [N, KV, bs, Dh]
        out = np.zeros((3, KV, BS, Dh), np.float32)
        for j, blk in enumerate(table[0]):
            out[blk] = a[j * BS:(j + 1) * BS].transpose(1, 0, 2)
        return jnp.asarray(out)

    if kernel == "decode":  # one query a row, mid-block: it sees its block's end
        q_pos = np.array([17])
        q = rng.standard_normal((1, H, Dh)).astype(np.float32)
        got = paged_flash_attend(
            jnp.asarray(q)[:, None], pool_of(k), pool_of(v), jnp.asarray(table),
            jnp.asarray(q_pos, jnp.int32), block=B, interpret=True)[:, 0]
        causal = paged_flash_attend(
            jnp.asarray(q)[:, None], pool_of(k), pool_of(v), jnp.asarray(table),
            jnp.asarray(q_pos, jnp.int32), interpret=True)[:, 0]
    else:
        q_pos = np.arange(16, 24)  # a tile of two blocks
        q = rng.standard_normal((8, H, Dh)).astype(np.float32)
        meta = jnp.asarray([[0, 16, 8, P.RAGGED_PREFILL]], jnp.int32)
        if kernel == "ragged":
            def run(block):
                return ragged_paged_attend(
                    jnp.asarray(q), pool_of(k), pool_of(v), jnp.asarray(table),
                    meta, block=block, interpret=True)
        else:
            def run(block):
                cfg = _cfg(diffusion_block=block, mask_token_id=255,
                           n_heads=H, n_kv_heads=KV, head_dim_override=Dh)
                return P._ragged_attend_xla(
                    cfg, jnp.asarray(q)[:, None], pool_of(k)[None],
                    pool_of(v)[None], 0, jnp.asarray(table),
                    jnp.zeros((8,), jnp.int32), jnp.asarray(q_pos, jnp.int32),
                    None)[:, 0]
        got, causal = run(B), run(0)
    want = _dense(q, k, v, q_pos, B)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # and it is not the causal answer (the rule is in the kernel)
    assert np.abs(np.asarray(causal) - want).max() > 1e-2


# ---- the routed layer ---------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_routed_softmax_layer_agrees_with_every_expert_moe_ffn(dtype):
    """The two forms of one layer: llama.moe_ffn computes every expert on
    every token under a mask of the weights, routed_mlp each token through
    its chosen experts alone."""
    cfg = get_model_config("test-moe-tiny", dtype=dtype)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.dim), cfg.jnp_dtype)
    want = llama.moe_ffn(cfg, lp, h)
    routed = cfg.replace(moe_ffn_dim=cfg.ffn_dim)
    banks = {n: params["layers"][n] for n in llama.BANKS}
    got, sizes = llama.routed_mlp(routed, lp, h, banks, jnp.int32(1), None)
    tol = 2e-5 if dtype == "float32" else 6e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert int(sizes.sum()) == 2 * 9 * cfg.n_experts_per_tok
    live = jnp.arange(18) < 5  # rows nothing reads reach no expert
    _, sizes = llama.routed_mlp(routed, lp, h, banks, jnp.int32(1), live)
    assert int(sizes.sum()) == 5 * cfg.n_experts_per_tok


def test_route_scores_both_families():
    from distributed_llm_inference_tpu.models import experts

    cfg = _cfg()
    h = jax.random.normal(jax.random.PRNGKey(0), (7, cfg.dim))
    w = jax.random.normal(jax.random.PRNGKey(1), (cfg.dim, cfg.n_experts))
    chosen, weights = experts.route(cfg, h, w)
    p = jax.nn.softmax(h @ w, axis=-1)
    top, idx = jax.lax.top_k(p, cfg.n_experts_per_tok)
    assert (np.asarray(chosen) == np.asarray(idx)).all()
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(top / top.sum(-1, keepdims=True)), rtol=1e-5)
    mla = get_model_config("test-mla-moe-tiny")
    assert (cfg.router_score, mla.router_score) == ("softmax", "sigmoid")
    with pytest.raises(AttributeError):  # the sigmoid score wants its bias
        experts.route(mla, h, jax.random.normal(jax.random.PRNGKey(1),
                                                (cfg.dim, mla.n_experts)))


def test_the_program_and_the_reference_draw_the_same_weights():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    rp = ref_params(cfg, SEED)
    for name in llama.ROUTED_LEAF_KEYS:
        a = params["layers"][name] if name in params["layers"] else params[name]
        b = rp[name] if name in ("embed", "lm_head") else jnp.stack(rp[name])
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert llama.ROUTED_LEAF_KEYS == REF.LEAF_KEY


# ---- the reference's row convention --------------------------------------------

def _replay(cfg, steps, prompt, n_new):
    """Generate step by step with the reference's layer, one denoise state
    a pass (earlier blocks clean, the open block as it stands): the tokens,
    and each token's logits when it was revealed."""
    config, rp = ref_config(cfg, steps), ref_params(cfg, SEED)
    s = REF.sizes(config)
    B, count = s["B"], s["B"] // steps
    seq = list(prompt[: len(prompt) // B * B])
    block = list(prompt[len(seq):]) + [s["mask"]] * (B - len(prompt) + len(seq))
    out, rows = [], []
    step = jax.jit(lambda x, lp: REF.layer(
        x, lp, H=s["H"], KV=s["KV"], Dh=s["Dh"], B=B, theta=s["theta"],
        eps=s["eps"], k=s["k"], renorm=s["renorm"]))
    while len(out) < n_new:
        toks = seq + block
        nb = -(-(len(toks) // B) // REF.BLOCKS_A_PASS) * REF.BLOCKS_A_PASS
        pad = jnp.full((nb * B,), s["mask"], jnp.int32).at[:len(toks)].set(
            jnp.asarray(toks, jnp.int32))
        with jax.default_matmul_precision("highest"):
            x = rp["embed"][pad.reshape(nb, 1, B)].astype(jnp.float32)
            for l in range(s["L"]):
                x = step(x, {n: rp[n][l] for n in REF.LAYER_LEAVES})
        lg = np.asarray(REF.logits(config, rp, x[len(seq) // B, 0]))
        masked = [i for i, t in enumerate(block) if t == s["mask"]]
        for i in masked[:count]:
            block[i] = int(lg[i].argmax())
            rows.append(lg[i])
        if s["mask"] not in block:
            out += block[len(prompt) - len(seq):] if not out else block
            seq, block = seq + block, [s["mask"]] * B
    return out[:n_new], np.stack(rows)[:n_new]


@pytest.mark.parametrize("steps,n_prompt", [(2, 8), (2, 10), (4, 7), (1, 9)])
def test_reference_rows_against_a_step_by_step_replay(steps, n_prompt):
    cfg = _cfg()
    prompt = [int(t) for t in np.random.default_rng(5).integers(3, 250, n_prompt)]
    out, rows = _replay(cfg, steps, prompt, 11)
    lg = ref_logits(cfg, SEED, steps, prompt + out, n_prompt)
    np.testing.assert_allclose(lg, rows, atol=2e-5)
    assert (lg.argmax(-1) == np.asarray(out)).all()


# ---- the step program against the reference's logits ----------------------------

MIXED_LOGITS = []  # one list: a compiled program keeps what it was traced with


@pytest.fixture
def mixed_logits(monkeypatch):
    """The logits a mixed launch hands `diffusion_epilogue`, out of the
    compiled program (a Rows with via="mixed" names its configuration apart,
    so its programs are traced with this in place)."""
    inner = P.diffusion_epilogue

    def epilogue(cfg, state, sparams, diff, logits, *rest):
        jax.debug.callback(lambda a: MIXED_LOGITS.append(np.asarray(a)), logits)
        return inner(cfg, state, sparams, diff, logits, *rest)

    monkeypatch.setattr(P, "diffusion_epilogue", epilogue)
    yield
    MIXED_LOGITS.clear()


@functools.partial(jax.jit, static_argnames=("cfg",))
def _forward_blocks(cfg, params, state, diff, pool, table):
    """The decode chunk's forward, one program a configuration (a jit made
    anew at every forward compiled it anew: 380 s of this file's 547 until
    ISSUE 47). A mutant names its configuration apart, so its program is
    traced with the rule taken out."""
    return P._forward_blocks_paged(cfg, params, state, diff, pool, table)


class Rows:
    """Rows through the step programs by hand, each with its own prompt,
    denoise_steps and budget, so one forward holds rows in different phases:
    chunked prefill of each prompt's whole blocks into the pool, then
    forwards through the decode chunk's body (`_forward_blocks_paged` +
    `diffusion_step`) or through `mixed_step_ragged` (`mutant` takes one
    rule out)."""

    TILE = 8

    def __init__(self, cfg, asks, mutant=None, via="chunk", chunk=16):
        self.cfg, self.Bd, self.mutant, self.via = cfg, cfg.diffusion_block, mutant, via
        self.asks = asks
        self.params = M.init_params(cfg, jax.random.PRNGKey(SEED))
        R = len(asks)
        self.table = jnp.asarray(
            [[1 + 4 * r + j for j in range(4)] for r in range(R)], jnp.int32)
        self.pool = self.prefill([p for p, _, _ in asks], chunk)
        state, self.sparams = G.init_slots(R, cfg.vocab_size)
        diff = P.init_diffusion(cfg, R)
        opens, whole = [], []
        for prompt, _, _ in asks:
            whole.append(len(prompt) // self.Bd * self.Bd)
            head = list(prompt[whole[-1]:])
            opens.append(head + [cfg.mask_token_id] * (self.Bd - len(head)))
        self.state = state._replace(
            pos=jnp.asarray(whole, jnp.int32), active=jnp.ones((R,), bool),
            remaining=jnp.asarray([mt for _, _, mt in asks], jnp.int32))
        self.diff = diff._replace(
            open=jnp.asarray(opens, jnp.int32),
            skip=jnp.asarray([len(p) - w for (p, _, _), w in zip(asks, whole)], jnp.int32),
            reveal=jnp.asarray([self.Bd // st for _, st, _ in asks], jnp.int32))

    def prefill(self, seqs, chunk=16):
        """A pool with each row's `seqs[r]` (whole blocks of it) landed by
        clean chunked prefill under the block mask."""
        cfg = self.cfg
        pool = P.init_pool(cfg, 1 + 4 * len(seqs), BS)
        for r, seq in enumerate(seqs):
            whole = len(seq) // self.Bd * self.Bd
            for at in range(0, whole, chunk):
                n = min(chunk, whole - at)
                meta, tok_row, tok_pos, _, _ = P.build_ragged_meta(
                    [(r, at, n, P.RAGGED_PREFILL)], width=chunk, tile=self.TILE)
                toks = np.zeros((chunk,), np.int32)
                toks[:n] = seq[at:at + n]
                pool = P.extend_ragged_paged(
                    cfg, self.params, jnp.asarray(toks), jnp.asarray(tok_row),
                    jnp.asarray(tok_pos), jnp.asarray(meta), pool, self.table)
        return pool

    def _chunk_forward(self, key):
        cfg = self.cfg
        logits, self.pool = _forward_blocks(
            cfg, self.params, self.state, self.diff, self.pool, self.table)
        if self.mutant == "shifted-read":  # the autoregressive habit
            logits = jnp.roll(logits, 1, axis=1)
        self.state, self.diff, emit, ok = P.diffusion_step(
            cfg, self.state, self.sparams, self.diff, logits, key)
        return np.asarray(logits), np.asarray(emit), np.asarray(ok)

    def _mixed_forward(self, key):
        """The host's half of a mixed launch that carries every live row (the
        plan engine/continuous._launch_mixed makes from its position model,
        here from the device's own state)."""
        cfg, Bd, R = self.cfg, self.Bd, len(self.asks)
        W = (R + 1) * self.TILE
        live = np.flatnonzero(np.asarray(self.state.active))
        owing = [bool(self.diff.owe[r]) and self.mutant != "mixed-drops-owed" for r in live]
        pos = np.asarray(self.state.pos)
        entries = [(int(r), int(pos[r]) - Bd * o, Bd * (1 + o), P.RAGGED_PREFILL)
                   for r, o in zip(live, owing)]
        meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
            entries, width=W, tile=self.TILE)
        *dev, open_at = P.build_block_meta(
            entries, offsets, owing, block=Bd, width=W, tile=self.TILE)
        dec_idx = np.full((R,), -1, np.int32)
        dec_idx[live] = open_at
        packed, self.state, self.sparams, self.pool, self.diff = P.mixed_step_ragged(
            cfg, self.params, jnp.zeros((W,), jnp.int32), jnp.asarray(tok_row),
            jnp.asarray(tok_pos), jnp.zeros((W,), bool), jnp.asarray(meta), self.pool,
            self.table, self.state, self.sparams, key, jnp.asarray(dec_idx),
            P.idle_mixed_arm(R, cfg.vocab_size), dev=P.DeviceMeta(*map(jnp.asarray, dev)),
            diff=self.diff, darm=P.init_diffusion(cfg, R))
        packed = np.asarray(packed)
        jax.effects_barrier()
        return MIXED_LOGITS[-1], packed[:Bd].T, packed[Bd:2 * Bd].T.astype(bool)

    def run(self):
        """Per row: (tokens emitted, the logits each was revealed from)."""
        cfg, R = self.cfg, len(self.asks)
        out, rows = [[] for _ in range(R)], [[] for _ in range(R)]
        self.phases = set()  # (masks left, owes) of the rows of each forward
        for it in range(64):
            active = np.asarray(self.state.active)
            if not active.any():
                break
            if self.mutant == "owed-not-rewritten":
                # the open block reads the K/V its predecessor's last denoise
                # forward left, computed from masks
                self.diff = self.diff._replace(owe=jnp.zeros((R,), bool))
            before, was = np.asarray(self.diff.open), np.asarray(self.state.pos)
            self.phases.add(tuple(
                (int((before[r] == cfg.mask_token_id).sum()), bool(self.diff.owe[r]))
                for r in np.flatnonzero(active)))
            forward = self._chunk_forward if self.via == "chunk" else self._mixed_forward
            logits, emit, ok = forward(jax.random.PRNGKey(it))
            clean = np.asarray(self.state.pos) > was
            after = np.where(clean[:, None], np.asarray(self.diff.owed),
                             np.asarray(self.diff.open))
            for r in np.flatnonzero(active):
                shown = (before[r] == cfg.mask_token_id) & (after[r] != cfg.mask_token_id)
                rows[r] += [logits[r, i] for i in np.flatnonzero(shown)]
                out[r] += [int(t) for t, o in zip(emit[r], ok[r]) if o]
        return out, rows


def _against_reference(cfg, prompt, steps, out, rows):
    lg = ref_logits(cfg, SEED, steps, prompt + out, len(prompt))
    got = np.stack(rows)[: len(out)]
    got[:, cfg.mask_token_id] = got.min(axis=-1)  # -inf there, by design
    return float(np.abs(got - lg).max()), margins(lg, out)


def _ask(steps, n_prompt, max_tokens):
    return ([int(t) for t in np.random.default_rng(n_prompt).integers(3, 250, n_prompt)],
            steps, max_tokens)


# every head (prompt length mod 4) with every denoise_steps, two rows a
# forward: prompts shorter than a block and of several prefill chunks, budgets
# that end inside a block and on its edge
PAIRS = [((1, 20, 14), (2, 21, 9)), ((4, 22, 12), (1, 23, 10)), ((2, 36, 8), (4, 37, 13)),
         ((1, 2, 6), (2, 3, 6)), ((4, 24, 7), (1, 45, 8)), ((2, 18, 11), (4, 19, 9))]


@pytest.mark.parametrize("via", ["chunk", "mixed"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("pair", PAIRS, ids=lambda p: "-".join(f"s{s}h{n % 4}" for s, n, _ in p))
def test_step_program_logits_equal_the_references_at_every_denoise_state(
        impl, via, pair, mixed_logits):
    """Chunked block-masked prefill, then denoise forwards that carry the
    owed block in front of the open one, through the decode chunk's body and
    through the mixed launch, two rows in different phases a forward: the
    logits a token is revealed from are the reference's at that denoise
    state, and what the pool holds for every block but a row's last is what
    a clean prefill of the same tokens writes."""
    cfg = _cfg(impl) if via == "chunk" else _cfg(impl).replace(name="test-sdar-tiny-mixed")
    asks = [_ask(*a) for a in pair]
    rows_ = Rows(cfg, asks, via=via)
    outs, rows = rows_.run()
    # some forward held one row that owed a block beside one that did not,
    # or rows with different numbers of masks left
    assert any(len(set(ph)) > 1 for ph in rows_.phases), rows_.phases
    seqs = []
    for r, (prompt, steps, max_tokens) in enumerate(asks):
        assert len(outs[r]) == max_tokens  # exactly, also where the budget ends mid-block
        worst, m = _against_reference(cfg, prompt, steps, outs[r], rows[r])
        assert worst < 5e-5 and m.max() == 0.0
        # the device's length is the prompt's whole blocks plus the clean blocks
        head = len(prompt) % 4
        end = len(prompt) - head + 4 * -(-(head + max_tokens) // 4)
        assert int(rows_.state.pos[r]) == end and not bool(rows_.diff.owe[r])
        seqs.append((prompt + outs[r] + [5] * 4)[:end])
    # the pool: every block but the last is COMMITTED (a clean prefill's
    # K/V); the last never is (what its last denoise forward wrote stands)
    clean = rows_.prefill(seqs)
    for r, seq in enumerate(seqs):
        for name in ("k", "v"):
            got, want = (np.asarray(P._gather_blocks(p[name], rows_.table[r]))[:, 0]
                         for p in (rows_.pool, clean))  # [L, KV, S, Dh]
            last = len(seq) - 4
            np.testing.assert_allclose(got[:, :, :last], want[:, :, :last], atol=2e-5)
            assert np.abs(got[:, :, last:len(seq)] - want[:, :, last:len(seq)]).max() > 1e-3


MUTANTS = ["causal-mask", "owed-not-rewritten", "shifted-read", "logits-at-owed",
           "mixed-drops-owed"]


@pytest.mark.parametrize("mutant", MUTANTS)
def test_the_comparison_fails_once_a_rule_is_taken_out(mutant, monkeypatch, mixed_logits):
    cfg = _cfg().replace(name=f"test-sdar-tiny-{mutant}")  # its own programs
    if mutant == "causal-mask":
        monkeypatch.setattr(P, "block_frontier", lambda q_pos, block=0: q_pos)
    if mutant == "logits-at-owed":  # the tile's first block, whichever it is
        inner = P.open_block_rows
        monkeypatch.setattr(P, "open_block_rows", lambda x, at, Bd: inner(
            x, at // (2 * Bd) * (2 * Bd), Bd))
    prompt, steps, _ = ask = _ask(2, 20, 16)
    via = "mixed" if mutant == "mixed-drops-owed" else "chunk"
    outs, rows = Rows(cfg, [ask], mutant=mutant, via=via).run()
    worst, m = _against_reference(cfg, prompt, steps, outs[0], rows[0])
    assert worst > 1e-2 and (m > 0).mean() > 0.2


def _one_block(cfg, open_, **state_kw):
    n = len(open_)
    state, sparams = G.init_slots(n, cfg.vocab_size)
    state = state._replace(active=jnp.ones((n,), bool), **state_kw)
    return state, sparams, P.init_diffusion(cfg, n)._replace(
        open=jnp.asarray(open_, jnp.int32))


def test_the_mask_id_is_never_chosen_and_never_emitted():
    cfg = _cfg()
    state, sparams, diff = _one_block(
        cfg, [[cfg.mask_token_id] * 4], remaining=jnp.asarray([8], jnp.int32))
    logits = jnp.zeros((1, 4, cfg.vocab_size)).at[:, :, cfg.mask_token_id].set(9.0)
    logits = logits.at[:, :, 17].set(1.0)
    # ONE forward (the whole block: the default) reveals, emits and moves on
    state, diff, emit, ok = P.diffusion_step(
        cfg, state, sparams, diff, logits, jax.random.PRNGKey(0))
    assert np.asarray(ok).all() and (np.asarray(emit) == 17).all()
    assert int(state.pos[0]) == 4 and int(state.remaining[0]) == 4
    assert (np.asarray(diff.open) == cfg.mask_token_id).all()  # the next block
    # and the clean block is owed its commit, token for token
    assert bool(diff.owe[0]) and (np.asarray(diff.owed) == 17).all()


def test_diffusion_step_stop_token_and_rows_that_did_not_ride():
    """The forward that reveals a block's last mask emits it. Row 0's comes
    clean with a stop token in it: it emits up to the stop, ends, and owes
    nothing (its last block is never committed). Row 1 comes clean and goes
    on: it owes the block. Row 2 reveals one of two masks: nothing emitted.
    Row 3 did not ride: untouched, what it owed still owed."""
    cfg = _cfg()
    mk, eos = cfg.mask_token_id, cfg.eos_token_id
    state, sparams, diff = _one_block(
        cfg, [[5, 6, mk, mk], [5, 6, 7, mk], [5, 6, mk, mk], [5, mk, mk, mk]],
        remaining=jnp.asarray([9, 9, 9, 9], jnp.int32))
    diff = diff._replace(reveal=jnp.asarray([2, 1, 1, 2], jnp.int32),
                         owe=jnp.asarray([True, True, False, True]),
                         owed=jnp.full((4, 4), 9, jnp.int32))
    logits = jnp.zeros((4, 4, cfg.vocab_size)).at[:, :, 8].set(1.0)
    logits = logits.at[0, 2, eos].set(2.0)
    new, d2, emit, ok = P.diffusion_step(
        cfg, state, sparams, diff, logits, jax.random.PRNGKey(0),
        on=jnp.asarray([True, True, True, False]))
    assert np.asarray(ok).tolist() == [[True, True, False, False], [True] * 4,
                                       [False] * 4, [False] * 4]
    assert np.asarray(emit[1]).tolist() == [5, 6, 7, 8]
    assert np.asarray(new.active).tolist() == [False, True, True, True]
    assert np.asarray(new.pos).tolist() == [4, 4, 0, 0]
    assert np.asarray(new.remaining).tolist() == [7, 5, 9, 9]
    assert np.asarray(d2.owe).tolist() == [False, True, False, True]
    assert np.asarray(d2.owed).tolist() == [[5, 6, eos, 8], [5, 6, 7, 8], [9] * 4, [9] * 4]
    assert np.asarray(d2.open).tolist() == [[mk] * 4, [mk] * 4, [5, 6, 8, mk], [5, mk, mk, mk]]


@pytest.mark.parametrize("owe", [False, True])
def test_a_forward_carries_the_owed_block_or_launch_padding(owe):
    """`block_row_layout`, the flat axis of a decode chunk's forward: 2 x
    block positions a row; the owed block from state.pos - block in front of
    the open one, or the open block from state.pos and four positions of
    launch padding (no row: not walked, written to the trash block, no
    expert); a row that is not active carries nothing."""
    cfg = _cfg()
    mk = cfg.mask_token_id
    state, _, diff = _one_block(cfg, [[5, 6, mk, mk]] * 2, pos=jnp.asarray([8, 8], jnp.int32))
    state = state._replace(active=jnp.asarray([True, False]))
    diff = diff._replace(owe=jnp.asarray([owe, True]), owed=jnp.full((2, 4), 9, jnp.int32))
    toks, tok_row, tok_pos, meta, open_at = map(np.asarray, P.block_row_layout(state, diff))
    if owe:
        assert meta[0].tolist() == [0, 4, 8, P.RAGGED_PREFILL] and open_at[0] == 4
        assert toks[:8].tolist() == [9, 9, 9, 9, 5, 6, mk, mk]
        assert tok_pos[:8].tolist() == list(range(4, 12))
        assert tok_row.tolist() == [0] * 8 + [-1] * 8
    else:
        assert meta[0].tolist() == [0, 8, 4, P.RAGGED_PREFILL] and open_at[0] == 0
        assert toks[:4].tolist() == [5, 6, mk, mk]
        assert tok_pos[:4].tolist() == list(range(8, 12))
        assert tok_row.tolist() == [0] * 4 + [-1] * 12
    assert meta[1, 2] == 0  # the row that is not active: q_len 0


def test_a_diffusion_model_is_refused_where_it_cannot_be_served():
    from distributed_llm_inference_tpu import create_engine
    from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine

    from distributed_llm_inference_tpu import EngineConfig

    eng = create_engine("test-sdar-tiny", seed=SEED,
                        engine_cfg=EngineConfig(prefix_cache_entries=8))
    out = eng.generate("hello", max_tokens=4)
    assert out["error_type"] == "invalid_request" and "continuous" in out["error"]
    with pytest.raises(ValueError, match="chunked ragged paged"):
        ContinuousEngine(eng, n_slots=2)  # a dense fleet
    with pytest.raises(ValueError, match="multiples of the diffusion block"):
        ContinuousEngine(eng, n_slots=2, kv_pool_blocks=24, kv_block_size=6,
                         kv_shadow=False)
    with pytest.raises(ValueError, match="does not carry"):
        ContinuousEngine(eng, n_slots=2, kv_pool_blocks=24, kv_block_size=16,
                         kv_shadow=True)  # the shadow store copies K/V pairs
