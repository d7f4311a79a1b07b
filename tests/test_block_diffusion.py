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

class Row:
    """One row through the step programs by hand: chunked prefill of the
    prompt's whole blocks into the pool, then forwards of the open block
    (`mutant` takes one rule out)."""

    def __init__(self, cfg, prompt, steps, max_tokens, mutant=None, chunk=16):
        self.cfg, self.Bd, self.mutant = cfg, cfg.diffusion_block, mutant
        self.params = M.init_params(cfg, jax.random.PRNGKey(SEED))
        self.table = jnp.asarray([[0] * 4, [1, 2, 3, 4]], jnp.int32)
        pool = P.init_pool(cfg, 6, BS)
        whole = len(prompt) // self.Bd * self.Bd
        for at in range(0, whole, chunk):
            n = min(chunk, whole - at)
            meta, tok_row, tok_pos, _, _ = P.build_ragged_meta(
                [(1, at, n, P.RAGGED_PREFILL)], width=chunk, tile=8)
            toks = np.zeros((chunk,), np.int32)
            toks[:n] = prompt[at:at + n]
            pool = P.extend_ragged_paged(
                cfg, self.params, jnp.asarray(toks), jnp.asarray(tok_row),
                jnp.asarray(tok_pos), jnp.asarray(meta), pool, self.table)
        self.pool = pool
        state, self.sparams = G.init_slots(2, cfg.vocab_size)
        self.state = state._replace(
            pos=state.pos.at[1].set(whole), active=state.active.at[1].set(True),
            remaining=state.remaining.at[1].set(max_tokens))
        head = list(prompt[whole:])
        diff = P.init_diffusion(cfg, 2)
        self.diff = diff._replace(
            open=diff.open.at[1].set(jnp.asarray(
                head + [cfg.mask_token_id] * (self.Bd - len(head)))),
            skip=diff.skip.at[1].set(len(head)),
            reveal=diff.reveal.at[1].set(self.Bd // steps))

    def run(self):
        """(tokens emitted, the logits each was revealed from)."""
        cfg = self.cfg
        fwd = jax.jit(lambda st, df, pl: P._forward_blocks_paged(
            cfg, self.params, st, df, pl, self.table))
        out, rows = [], []
        for it in range(64):
            if not bool(self.state.active[1]):
                break
            logits, pool = fwd(self.state, self.diff, self.pool)
            if self.mutant == "shifted-read":  # the autoregressive habit
                logits = jnp.roll(logits, 1, axis=1)
            before = np.asarray(self.diff.open[1])
            clean = (before != cfg.mask_token_id).all()
            if self.mutant == "no-commit" and clean:
                pool = self.pool  # the block's K/V stays the last denoise state's
            self.pool = pool
            self.state, self.diff, emit, ok = P.diffusion_step(
                cfg, self.state, self.sparams, self.diff, logits,
                jax.random.PRNGKey(it))
            after = np.asarray(self.diff.open[1])
            for i in np.flatnonzero((before != after) & ~clean):
                rows.append(np.asarray(logits[1, i]))
            out += [int(t) for t, o in zip(np.asarray(emit[1]), np.asarray(ok[1])) if o]
        return out, rows


def _against_reference(cfg, prompt, steps, out, rows):
    lg = ref_logits(cfg, SEED, steps, prompt + out, len(prompt))
    got = np.stack(rows)[: len(out)]
    got[:, cfg.mask_token_id] = got.min(axis=-1)  # -inf there, by design
    return float(np.abs(got - lg).max()), margins(lg, out)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("steps,n_prompt,max_tokens", [
    (2, 20, 14), (2, 21, 9), (4, 22, 12), (1, 23, 10), (2, 3, 6), (2, 37, 8)])
def test_step_program_logits_equal_the_references_at_every_denoise_state(
        impl, steps, n_prompt, max_tokens):
    """Chunked block-masked prefill, denoise forwards and commits through
    the paged pool, a prompt of every remainder mod 4, a budget that ends
    inside a block: the logits a token is revealed from are the
    reference's at that denoise state."""
    cfg = _cfg(impl)
    prompt = [int(t) for t in np.random.default_rng(n_prompt).integers(3, 250, n_prompt)]
    row = Row(cfg, prompt, steps, max_tokens)
    out, rows = row.run()
    assert len(out) == max_tokens  # exactly, also where the budget ends mid-block
    worst, m = _against_reference(cfg, prompt, steps, out, rows)
    assert worst < 5e-5 and m.max() == 0.0
    # the device's length is the prompt's whole blocks plus the blocks committed
    head = n_prompt % 4
    assert int(row.state.pos[1]) == n_prompt - head + 4 * -(-(head + max_tokens) // 4)


@pytest.mark.parametrize("mutant", ["causal-mask", "no-commit", "shifted-read"])
def test_the_comparison_fails_once_a_rule_is_taken_out(mutant, monkeypatch):
    cfg = _cfg().replace(name=f"test-sdar-tiny-{mutant}")  # its own programs
    if mutant == "causal-mask":
        monkeypatch.setattr(P, "block_frontier", lambda q_pos, block=0: q_pos)
    prompt = [int(t) for t in np.random.default_rng(1).integers(3, 250, 20)]
    out, rows = Row(cfg, prompt, 2, 16, mutant=mutant).run()
    worst, m = _against_reference(cfg, prompt, 2, out, rows)
    assert worst > 1e-2 and (m > 0).mean() > 0.2


def test_the_mask_id_is_never_chosen_and_never_emitted():
    cfg = _cfg()
    state, sparams = G.init_slots(1, cfg.vocab_size)
    state = state._replace(active=state.active.at[0].set(True),
                           remaining=state.remaining.at[0].set(8))
    diff = P.init_diffusion(cfg, 1)
    logits = jnp.zeros((1, 4, cfg.vocab_size)).at[:, :, cfg.mask_token_id].set(9.0)
    logits = logits.at[:, :, 17].set(1.0)
    for it in range(2):  # a denoise forward (the whole block: the default), a commit
        state, diff, emit, ok = P.diffusion_step(
            cfg, state, sparams, diff, logits, jax.random.PRNGKey(it))
    assert np.asarray(ok).all() and (np.asarray(emit) == 17).all()
    assert int(state.pos[0]) == 4 and int(state.remaining[0]) == 4
    assert (np.asarray(diff.open) == cfg.mask_token_id).all()  # the next block


def test_diffusion_step_stop_token_and_rows_that_did_not_ride():
    cfg = _cfg()
    state, sparams = G.init_slots(2, cfg.vocab_size)
    state = state._replace(active=jnp.asarray([True, True]),
                           remaining=jnp.asarray([9, 9], jnp.int32))
    diff = P.init_diffusion(cfg, 2)._replace(
        open=jnp.asarray([[5, 6, cfg.eos_token_id, 7], [5, 6, 7, 8]], jnp.int32))
    logits = jnp.zeros((2, 4, cfg.vocab_size))
    new, d2, emit, ok = P.diffusion_step(
        cfg, state, sparams, diff, logits, jax.random.PRNGKey(0),
        on=jnp.asarray([True, False]))
    # row 0 commits up to its stop token and ends; row 1 did not ride: untouched
    assert np.asarray(ok).tolist() == [[True, True, False, False], [False] * 4]
    assert np.asarray(new.active).tolist() == [False, True]
    assert np.asarray(new.pos).tolist() == [4, 0]
    assert (np.asarray(d2.open[1]) == np.asarray(diff.open[1])).all()


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
