"""The afmoe family (Trinity: gated GQA, sliding-window and global layers,
routed experts beside a shared one, one chip's share of them) against the
plain reference at `test-trinity-tiny`, seeded random weights, float32.

The tolerance of every logit comparison is 2e-5 of the logits' spread: the
program and the reference compute the same float32 arithmetic in another
order (flat tokens and grouped products against whole sequences and a dense
mask), which at these sizes differs by a few 1e-6; a wrong mask, a rotation
on a global layer or an expert left in or out moves a logit by 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.models import stack
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models.registry import get_model_config

from afmoe_util import REF, launch, ref_config, ref_logits, ref_params

SEED, TOL = 5, 2e-5
CFG = get_model_config("test-trinity-tiny")
SHARE = CFG.replace(name="test-trinity-share", expert_lo=2, n_experts_held=4)


def ids_of(n, salt=0):
    return [int(t) for t in np.random.default_rng(77 * salt + n).integers(3, 250, n)]


def close(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max() / np.asarray(b).std())


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_whole_sequence_forward_agrees_with_the_reference(cfg):
    """(a): several windows long, every layer kind, the dense layer, the
    shared expert; under a share the pairs routed elsewhere left out on
    both sides."""
    ids = ids_of(70)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    logits, _ = M.forward(cfg, params, jnp.asarray([ids]),
                          M.init_kv_cache(cfg, 1, 80), 0)
    assert close(logits[0], ref_logits(cfg, SEED, ids)) < TOL


def test_a_share_draws_the_uncut_models_values():
    """init_params draws the held experts' slices and any number of
    vocabulary rows with the values the uncut draw gives them."""
    whole = M.init_params(CFG, jax.random.PRNGKey(SEED))
    part = M.init_params(SHARE.replace(vocab_size=64), jax.random.PRNGKey(SEED))
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(
            part["layers"]["moe"][name], whole["layers"]["moe"][name][:, 2:6])
    for name in ("embed", "head"):
        np.testing.assert_array_equal(part[name], whole[name][:64])
    np.testing.assert_array_equal(part["layers"]["moe"]["w_router"],
                                  whole["layers"]["moe"]["w_router"])
    # and the reference writes the same draw down
    ref = ref_params(SHARE, SEED)
    np.testing.assert_array_equal(ref["w_gate"][1], whole["layers"]["moe"]["w_gate"][0, 2:6])
    np.testing.assert_array_equal(ref["lm_head"].T, whole["head"])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """(d): for one routed layer, the shares' routed parts plus the shared
    expert counted once are the uncut reference's layer."""
    cfg = CFG
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.dim), jnp.float32)
    layer, lm = 2, 1  # stack layer 2 is the expert stack's layer 1
    full = ref_params(cfg, SEED)
    lp = {n: full[n][layer] for n in REF.FFN_LEAVES["moe"]}
    router = dict(k=cfg.n_experts_per_tok, renorm=True, scaling=cfg.routed_scaling,
                  norm_eps=cfg.router_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = REF.routed_ffn(h[0], lp, lo=0, **router)
        shared = REF._swiglu(h[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, pairs = shared, 0
    for lo in range(0, 8, 2):  # four shares of two experts each
        part_cfg = cfg.replace(name=f"share{lo}", expert_lo=lo, n_experts_held=2)
        p = M.init_params(part_cfg, jax.random.PRNGKey(SEED))["layers"]["moe"]
        row = {n: leaf[lm] for n, leaf in p.items() if n not in stack.BANKS}
        out, sizes, away = stack.moe_ffn(
            part_cfg, row, {n: p[n] for n in stack.BANKS}, lm, h)
        with jax.default_matmul_precision("highest"):
            mine = stack.swiglu(h[0], row["ws_gate"], row["ws_up"], row["ws_down"])
        total = total + (out[0] - mine)
        pairs += int(sizes.sum())
        assert int(sizes.sum()) + int(away) == 24 * cfg.n_experts_per_tok
    assert pairs == 24 * cfg.n_experts_per_tok  # every pair in exactly one share
    assert close(total, want) < TOL
    del params


def test_a_global_layer_reads_no_positions_and_a_sliding_one_does():
    """(e): a full_attention layer takes no position encoding, so its last
    query's output does not change when the tokens before it change places;
    a sliding layer's does. (A uniform SHIFT of all positions would tell
    nothing: RoPE scores follow position differences alone, so a sliding
    layer is as indifferent to it as a global one.)"""
    cfg = CFG.replace(attn_window=64)  # (wide: the window cuts nothing here)
    lp = {n: leaf[0] for n, leaf in
          M.init_params(cfg, jax.random.PRNGKey(SEED))["layers"]["attn"].items()}
    from distributed_llm_inference_tpu.ops.attention import causal_mask
    from distributed_llm_inference_tpu.ops.rope import rope_cos_sin

    T, S = 12, 16
    h = jax.random.normal(jax.random.PRNGKey(2), (1, T, cfg.dim), jnp.float32)
    swapped = h[:, np.r_[np.random.default_rng(0).permutation(T - 1), T - 1]]

    def last(h, sliding):
        zeros = jnp.zeros((1, cfg.n_kv_heads, S, cfg.head_dim), jnp.float32)
        out, _, _ = stack.gated_attention(
            cfg if sliding else cfg.replace(attn_window=None), lp, h, zeros, zeros,
            jnp.int32(0),
            rope_cos_sin(jnp.arange(T), cfg.head_dim, cfg.rope_theta) if sliding else None,
            causal_mask(jnp.int32(0), T, S), stack.default_attn_hook, None)
        return np.asarray(out)[0, -1]

    # (the same terms summed in another order)
    np.testing.assert_allclose(last(h, False), last(swapped, False), atol=2e-6)
    assert np.abs(last(h, True) - last(swapped, True)).max() > 1e-3


def _pools(cfg, n_global, n_window, bs):
    return P.init_pool(cfg, (n_global, n_window), bs)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_decode_and_a_deep_prefix_hit_over_given_back_blocks(impl):
    """(b) and (c) at the level of the paged hooks, tables written by hand:
    a 50-token prompt prefilled in 16-token chunks, its window-group blocks
    below each launch's window pointed at the null block as the allocator
    would (window 8, blocks of 4), then decoded through the pool; every
    logit row against the reference. Then a second row maps the first 10
    global blocks and the 3 window blocks that overlap [33, 40) under a
    fresh tail: bit-equal to a cold prefill of the same tokens."""
    cfg = SHARE.replace(attn_impl=impl)
    bs, MB, W = 4, 20, cfg.attn_window
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    ids = ids_of(50)
    want = ref_logits(cfg, SEED, ids + ids_of(8, 1))

    def tables(rows):  # rows: [(global row, window row)] -> the launch table
        return np.concatenate([np.stack([g for g, _ in rows]),
                               np.stack([w for _, w in rows])], axis=1)

    g0 = np.zeros(MB, np.int32); g0[:15] = 1 + np.arange(15)
    w0 = np.zeros(MB, np.int32); w0[:15] = 1 + np.arange(15)
    idle = (np.zeros(MB, np.int32), np.zeros(MB, np.int32))
    pool = _pools(cfg, 40, 40, bs)
    got, start = [], 0
    for n in (16, 16, 16, 2):
        w_now = w0.copy()
        w_now[:max(0, (start - 1 - W + 1)) // bs] = 0  # given back after the last launch
        out, pool = launch(cfg, params, pool, tables([(g0, w_now), idle]),
                           [(0, start, ids[start:start + n], P.RAGGED_PREFILL)])
        got.append(out[0]); start += n
    seq = ids + ids_of(8, 1)
    for t in range(50, 58):  # decode rows, one token a launch
        w_now = w0.copy(); w_now[:max(0, t - 1 - W + 1) // bs] = 0
        out, pool = launch(cfg, params, pool, tables([(g0, w_now), idle]),
                           [(0, t, seq[t:t + 1], P.RAGGED_DECODE)])
        got.append(out[0])
    assert close(np.concatenate(got), want) < TOL
    # a hit at depth 40 (10 blocks): the global group's [0, 40), the window
    # group's blocks that overlap [40 - 7, 40) = logical 8 and 9
    tail = ids_of(9, 2)
    g1 = np.zeros(MB, np.int32); g1[:10] = g0[:10]; g1[10:14] = 20 + np.arange(4)
    w1 = np.zeros(MB, np.int32); w1[8:10] = w0[8:10]; w1[10:14] = 20 + np.arange(4)
    hit, pool = launch(cfg, params, pool, tables([idle, (g1, w1)]),
                       [(1, 40, tail, P.RAGGED_PREFILL)])
    cold_pool = _pools(cfg, 40, 40, bs)
    g2 = np.zeros(MB, np.int32); g2[:14] = 1 + np.arange(14)
    for st in (0, 16, 32):
        _, cold_pool = launch(cfg, params, cold_pool, tables([(g2, g2), idle]),
                              [(0, st, ids[st:min(st + 16, 40)], P.RAGGED_PREFILL)])
    cold, _ = launch(cfg, params, cold_pool, tables([(g2, g2), idle]),
                     [(0, 40, tail, P.RAGGED_PREFILL)])
    np.testing.assert_array_equal(hit[0], cold[0])
    assert close(hit[0], ref_logits(cfg, SEED, ids[:40] + tail)[40:]) < TOL


def test_a_uniform_configuration_is_one_group():
    """(g), the part a single checkout can hold: a model whose layers are
    all of one kind has one group, one table and the parent's pool leaves
    (the step programs of `mistral-7b-16l` and `lfm2-24b-a2b-9l` compare
    equal to the parent's: tests/dense_equal.py --compare-programs, PERF.md)."""
    for name in ("test-llama-tiny", "test-lfm2-tiny", "test-mla-moe-tiny",
                 "test-sdar-tiny"):
        cfg = get_model_config(name)
        assert cfg.kv_groups == ("global",)
        assert P.group_blocks(cfg, 100, 9, 4, 4) == (100,)
    one_kind = CFG.replace(name="all-sliding", layer_types=("sliding_attention",) * 5)
    assert one_kind.kv_groups == ("global",)
    assert set(P.init_pool(one_kind, 8, 4)) == {"k", "v", "routed"}
    assert set(P.init_pool(CFG, (8, 6), 4)) == {"k", "v", "kw", "vw", "routed"}
    assert P.init_pool(CFG, (8, 6), 4)["kw"].shape[:2] == (4, 6)
    # the slots' budgets where the window's share of the contexts is less;
    # a quarter at a window of 32 blocks (tests/test_mimo.py holds the
    # served counts of both grouped configurations)
    assert P.group_blocks(CFG, 100, 9, 4, 4) == (100, 37)
    assert P.group_blocks(CFG, 100, 7, 2, 4) == (100, 15)
    assert P.group_blocks(CFG.replace(attn_window=4096), 100, 3, 2, 128) == (100, 25)
