"""The mimo_v2 family (MiMo-V2.5: sliding-window layers with a learned sink
beside global layers, the two kinds with their own K/V head counts and
rotation bases, keys of 192 numbers and values of 128, routed experts with no
shared one, one chip's share of them) against the plain reference at
`test-mimo-tiny`, seeded random weights, float32.

The tolerance of every logit comparison is 2e-5 of the logits' spread: the
program and the reference compute the same float32 arithmetic in another
order (flat tokens, grouped products and an online softmax that starts at the
sink against whole sequences and a dense mask), which at these sizes differs
by a few 1e-6; a wrong mask, a sink with the wrong sign, the other kind's
rotation base or an expert left in or out moves a logit by 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.models import mimo_v2, stack
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models.registry import get_model_config
from distributed_llm_inference_tpu.ops.attention import attend
from distributed_llm_inference_tpu.ops.paged_attention import (
    paged_flash_attend, ragged_paged_attend)
from distributed_llm_inference_tpu.ops.rope import apply_rope, rope_cos_sin

from mimo_util import REF, launch, ref_config, ref_logits, ref_params

SEED, TOL = 5, 2e-5
CFG = get_model_config("test-mimo-tiny")
SHARE = CFG.replace(name="test-mimo-share", expert_lo=2, n_experts_held=4)


def ids_of(n, salt=0):
    return [int(t) for t in np.random.default_rng(91 * salt + n).integers(3, 250, n)]


def close(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max() / np.asarray(b).std())


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_whole_sequence_forward_agrees_with_the_reference(cfg):
    """Several windows long, both layer kinds, the dense layer; under a share
    the pairs routed elsewhere left out on both sides."""
    ids = ids_of(70)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    logits, _ = M.forward(cfg, params, jnp.asarray([ids]),
                          M.init_kv_cache(cfg, 1, 80), 0)
    assert close(logits[0], ref_logits(cfg, SEED, ids)) < TOL


def test_bfloat16_follows_the_reference_within_its_rounding():
    """The served dtype: weights rounded to bfloat16 on both sides, the
    program's products in bfloat16 with float32 sums against the reference's
    float32 arithmetic on the same rounded weights. A row's distance is the
    activations' rounding (8 bits of mantissa through 4 layers: 0.01-0.1 of
    the logits' spread, the median row 0.03), four orders above float32's;
    the rows are judged one by one because a near-tie in a router's top-2
    that rounds the other way sends a token through another expert and moves
    its row by the spread itself (2 of 70 rows here): nine rows in ten stay
    within 0.15, the median within 0.06, and nine in ten choose the
    reference's top-1. A wrong mask, sink or base moves EVERY row by > 0.5."""
    cfg, ids = CFG.replace(dtype="bfloat16"), ids_of(70)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    logits, _ = M.forward(cfg, params, jnp.asarray([ids]),
                          M.init_kv_cache(cfg, 1, 80), 0)
    got, want = np.asarray(logits[0]), ref_logits(cfg, SEED, ids, jnp.bfloat16)
    rows = np.abs(got - want).max(axis=1) / want.std()
    assert 1e-3 < np.median(rows) < 0.06
    assert np.mean(rows < 0.15) >= 0.9
    assert np.mean(got.argmax(1) == want.argmax(1)) >= 0.9


def test_a_share_draws_the_uncut_models_values():
    whole = M.init_params(CFG, jax.random.PRNGKey(SEED))
    part = M.init_params(SHARE.replace(vocab_size=64), jax.random.PRNGKey(SEED))
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(
            part["layers"]["moe"][name], whole["layers"]["moe"][name][:, 2:6])
    for name in ("embed", "head"):
        np.testing.assert_array_equal(part[name], whole[name][:64])
    for kind in ("global", "window"):
        for name, leaf in whole["layers"][kind].items():
            np.testing.assert_array_equal(part["layers"][kind][name], leaf)
    # and the reference writes the same draw down
    ref = ref_params(SHARE, SEED)
    np.testing.assert_array_equal(ref["w_gate"][1], whole["layers"]["moe"]["w_gate"][0, 2:6])
    np.testing.assert_array_equal(ref["lm_head"].T, whole["head"])
    np.testing.assert_array_equal(ref["sink"][2], whole["layers"]["window"]["sink"][1])
    np.testing.assert_array_equal(ref["wk"][3], whole["layers"]["global"]["wk"][1])
    assert ref["sink"][0] is None and ref["sink"][1].dtype == jnp.float32


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """For one routed layer the shares' routed parts (there is no shared
    expert to count once) are the uncut reference's layer, and every pair is
    in exactly one share."""
    cfg = CFG
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.dim), jnp.float32)
    layer, lm = 2, 1  # stack layer 2 is the expert stack's layer 1
    full = ref_params(cfg, SEED)
    lp = {n: full[n][layer] for n in REF.FFN_LEAVES["moe"]}
    with jax.default_matmul_precision("highest"):
        want = REF.routed_ffn(h[0], lp, lo=0, k=cfg.n_experts_per_tok,
                              renorm=True, scaling=1.0,
                              norm_eps=cfg.router_norm_eps)
    total, pairs = 0.0, 0
    for lo in range(8):  # eight shares of one expert each
        part_cfg = cfg.replace(name=f"share{lo}", expert_lo=lo, n_experts_held=1)
        p = M.init_params(part_cfg, jax.random.PRNGKey(SEED))["layers"]["moe"]
        row = {n: leaf[lm] for n, leaf in p.items() if n not in stack.BANKS}
        out, sizes, away = stack.moe_ffn(
            part_cfg, row, {n: p[n] for n in stack.BANKS}, lm, h)
        total = total + out[0]
        pairs += int(sizes.sum())
        assert int(sizes.sum()) + int(away) == 24 * cfg.n_experts_per_tok
    assert pairs == 24 * cfg.n_experts_per_tok
    assert close(total, want) < TOL


def test_the_rotation_turns_the_first_lanes_and_passes_the_rest():
    """ops/rope.apply_rope with tables narrower than the head: lanes 0 .. 63
    of 192 turn among themselves in the half-rotation form, lanes 64 .. 191
    come through bit for bit; whole-head tables are what they were."""
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 2, 192), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 1, 192), jnp.float32)
    pos = jnp.arange(7, 12)
    cos, sin = rope_cos_sin(pos, 64, 1e4)
    q2, k2 = apply_rope(q, k, cos, sin)
    np.testing.assert_array_equal(q2[..., 64:], q[..., 64:])
    np.testing.assert_array_equal(k2[..., 64:], k[..., 64:])
    with jax.default_matmul_precision("highest"):
        want = REF._rope(k[0], pos, 1e4, 64)
    np.testing.assert_allclose(k2[0], want, atol=1e-6)
    full = apply_rope(q[..., :64], k[..., :64], cos, sin)
    np.testing.assert_array_equal(full[0], q2[..., :64])


def _sink_case(tq_rows, KV, H, bs=8, MB=6, seed=0):
    """A pool of one layer at the published widths (keys 192 on 256 lanes,
    values 128) filled with random rows, float32, and per-row lengths."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    N = tq_rows * MB + 1
    pool_k = jnp.pad(jax.random.normal(ks[0], (1, N, KV, bs, 192)),
                     ((0, 0),) * 4 + ((0, 64),))
    pool_v = jax.random.normal(ks[1], (1, N, KV, bs, 128))
    table = 1 + jnp.arange(tq_rows * MB, dtype=jnp.int32).reshape(tq_rows, MB)
    sink = jax.random.normal(ks[2], (H,)) * 2.0
    return pool_k, pool_v, table, sink, ks[3:]


def _dense_view(pool, table_row):
    g = pool[0, table_row]  # [MB, KV, bs, D]
    return g.transpose(1, 0, 2, 3).reshape(1, g.shape[1], -1, g.shape[-1])


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("with_sink", [False, True])
def test_the_decode_kernel_takes_a_sink_and_two_widths(window, with_sink):
    """paged_flash_attend against ops/attention.attend on the gathered rows:
    K rows of 256 lanes, V rows of 128, a sink a query head, rows of
    different lengths, one of them inactive; written in place (the new token
    through the pool's aliased output)."""
    B, KV, H, bs, MB = 3, 2, 4, 8, 6
    pool_k, pool_v, table, sink, ks = _sink_case(B, KV, H, bs, MB)
    sink = sink if with_sink else None
    pos = jnp.asarray([5, 29, 40], jnp.int32)
    q = jnp.pad(jax.random.normal(ks[0], (B, 1, H, 192)), ((0, 0),) * 3 + ((0, 64),))
    nk = jnp.pad(jax.random.normal(ks[1], (B, 1, KV, 192)), ((0, 0),) * 3 + ((0, 64),))
    nv = jax.random.normal(ks[2], (B, 1, KV, 128))
    active = jnp.asarray([True, True, False])
    out, pk, pv = paged_flash_attend(
        q, pool_k, pool_v, table, pos, None, active,
        (jnp.int32(0), nk, nv), None, sink, window=window, scale=192 ** -0.5)
    assert out.shape == (B, 1, H, 128)
    for b in range(2):
        kd = _dense_view(pool_k, table[b]).at[0, :, pos[b]].set(nk[b, 0])
        vd = _dense_view(pool_v, table[b]).at[0, :, pos[b]].set(nv[b, 0])
        np.testing.assert_array_equal(_dense_view(pk, table[b]), kd)
        np.testing.assert_array_equal(_dense_view(pv, table[b]), vd)
        kv_pos = jnp.arange(MB * bs)
        mask = kv_pos <= pos[b]
        if window:
            mask &= kv_pos > pos[b] - window
        want = attend(q[b:b + 1], kd, vd, mask[None], scale=192 ** -0.5,
                      sink=sink)
        np.testing.assert_allclose(out[b], want[0], atol=2e-6)
    np.testing.assert_array_equal(out[2], 0.0)


@pytest.mark.parametrize("with_sink", [False, True])
def test_the_ragged_kernel_takes_a_sink_and_two_widths(with_sink):
    """ragged_paged_attend: a prefill chunk of 11 tokens from position 9
    (two tiles of 8, the second partly padding) and a decode row, a window
    of 12, against ops/attention.attend query by query."""
    R, KV, H, bs, MB, tq = 2, 2, 4, 8, 6, 8
    pool_k, pool_v, table, sink, ks = _sink_case(R, KV, H, bs, MB, seed=1)
    sink = sink if with_sink else None
    entries = [(0, 9, 11, P.RAGGED_PREFILL), (1, 30, 1, P.RAGGED_DECODE)]
    meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
        entries, width=32, tile=tq)
    W = 32
    q = jnp.pad(jax.random.normal(ks[0], (W, H, 192)), ((0, 0),) * 2 + ((0, 64),))
    nk = jnp.pad(jax.random.normal(ks[1], (W, KV, 192)), ((0, 0),) * 2 + ((0, 64),))
    nv = jax.random.normal(ks[2], (W, KV, 128))
    out, pk, pv = ragged_paged_attend(
        q, pool_k, pool_v, table, jnp.asarray(meta), None,
        (jnp.int32(0), nk, nv), None, sink, window=12, scale=192 ** -0.5)
    assert out.shape == (W, H, 128)
    for (row, start, n, _), off in zip(entries, offsets):
        kd, vd = _dense_view(pool_k, table[row]), _dense_view(pool_v, table[row])
        kd = kd.at[0, :, start:start + n].set(nk[off:off + n].swapaxes(0, 1))
        vd = vd.at[0, :, start:start + n].set(nv[off:off + n].swapaxes(0, 1))
        np.testing.assert_array_equal(_dense_view(pk, table[row]), kd)
        kv_pos = jnp.arange(MB * bs)
        for t in range(n):
            p = start + t
            mask = (kv_pos <= p) & (kv_pos > p - 12)
            want = attend(q[off + t][None, None], kd, vd, mask[None],
                          scale=192 ** -0.5, sink=sink)
            np.testing.assert_allclose(out[off + t], want[0, 0], atol=2e-6)


def test_a_layer_without_a_sink_compiles_to_what_it_did():
    """A global layer passes no sink, and the kernel's call is then the one
    every accepted configuration makes: the jaxpr of the decode kernel's
    wrapper at a head dim of 128 has no operand, scratch or equation that
    its form before the sink did not have (the sink's are one VMEM operand
    and two stores; without them the body's two initial stores are the
    constant fills)."""
    q = jnp.zeros((2, 1, 4, 128))
    pool = jnp.zeros((1, 9, 2, 8, 128))
    table = jnp.zeros((2, 4), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)

    def call(sink):
        return jax.make_jaxpr(lambda s: paged_flash_attend(
            q, pool, pool, table, pos, None, None,
            (jnp.int32(0), q[:, :, :2], q[:, :, :2]), None, s))(sink)

    plain, with_sink = str(call(None)), str(call(jnp.zeros((4,))))
    assert "sink" not in plain
    assert plain != with_sink and len(with_sink) > len(plain)
    # the same call twice is the same program; a sink is the only difference
    assert plain == str(call(None))


def _pools(cfg, n_global, n_window, bs):
    return P.init_pool(cfg, (n_global, n_window), bs)


def test_the_pools_groups_have_their_own_rows():
    pool = _pools(CFG, 9, 7, 8)
    assert pool["k"].shape == (2, 9, 1, 8, 256) and pool["v"].shape == (2, 9, 1, 8, 128)
    assert pool["kw"].shape == (2, 7, 2, 8, 256) and pool["vw"].shape == (2, 7, 2, 8, 128)
    assert pool["routed"].shape == (2, 3, 8)
    assert _pools(SHARE, 9, 7, 8)["routed"].shape == (2, 3, 5)
    big = get_model_config("mimo-v2.5")
    assert (big.key_row, big.value_dim) == (256, 128)
    assert [big.group_kv_heads(g) for g in big.kv_groups] == [4, 8]
    assert [len(big.group_layers(g)) for g in big.kv_groups] == [9, 39]
    # the window group follows the window: a quarter at 32 blocks, the
    # slots' budgets at one
    trinity = get_model_config("trinity-large-preview")
    assert P.group_blocks(trinity, 4608, 37, 16, 128) == (4608, 1152)
    assert P.group_blocks(big, 2304, 6, 32, 128) == (2304, 193)
    assert P.group_blocks(big, 2304 * 16, 6, 32, 128) == (2304 * 16, 288)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_decode_and_a_deep_prefix_hit_over_given_back_blocks(impl):
    """At the level of the paged hooks, tables written by hand: a 50-token
    prompt prefilled in 16-token chunks, its window-group blocks below each
    launch's window pointed at the null block as the allocator would (window
    16, blocks of 8), then decoded through the pool across three more given-
    back blocks; every logit row against the reference. Then a second row
    maps the first 5 global blocks and the 2 window blocks that overlap
    [25, 40) under a fresh tail: bit-equal to a cold prefill of the same
    tokens."""
    cfg = SHARE.replace(attn_impl=impl)
    bs, MB, W = 8, 12, cfg.attn_window
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    ids, more = ids_of(50), ids_of(30, 1)
    want = ref_logits(cfg, SEED, ids + more)

    def tables(rows):  # rows: [(global row, window row)] -> the launch table
        return np.concatenate([np.stack([g for g, _ in rows]),
                               np.stack([w for _, w in rows])], axis=1)

    g0 = np.zeros(MB, np.int32); g0[:11] = 1 + np.arange(11)
    w0 = np.zeros(MB, np.int32); w0[:11] = 1 + np.arange(11)
    idle = (np.zeros(MB, np.int32), np.zeros(MB, np.int32))
    pool = _pools(cfg, 30, 30, bs)
    got, start = [], 0
    for n in (16, 16, 16, 2):
        w_now = w0.copy()
        w_now[:max(0, (start - 1 - W + 1)) // bs] = 0  # given back after the last launch
        out, pool = launch(cfg, params, pool, tables([(g0, w_now), idle]),
                           [(0, start, ids[start:start + n], P.RAGGED_PREFILL)])
        got.append(out[0]); start += n
    seq, given_back = ids + more, set()
    for t in range(50, 80):  # decode rows, one token a launch
        lo = max(0, t - 1 - W + 1) // bs
        w_now = w0.copy(); w_now[:lo] = 0
        given_back.add(lo)
        out, pool = launch(cfg, params, pool, tables([(g0, w_now), idle]),
                           [(0, t, seq[t:t + 1], P.RAGGED_DECODE)])
        got.append(out[0])
    assert len(given_back) >= 4  # three more blocks given back while decoding
    assert close(np.concatenate(got), want) < TOL
    # a hit at depth 40 (5 blocks): the global group's [0, 40), the window
    # group's blocks that overlap [40 - 15, 40) = logical 3 and 4
    tail = ids_of(9, 2)
    g1 = np.zeros(MB, np.int32); g1[:5] = g0[:5]; g1[5:7] = 20 + np.arange(2)
    w1 = np.zeros(MB, np.int32); w1[3:5] = w0[3:5]; w1[5:7] = 20 + np.arange(2)
    hit, pool = launch(cfg, params, pool, tables([idle, (g1, w1)]),
                       [(1, 40, tail, P.RAGGED_PREFILL)])
    cold_pool = _pools(cfg, 30, 30, bs)
    g2 = np.zeros(MB, np.int32); g2[:7] = 1 + np.arange(7)
    for st in (0, 16, 32):
        _, cold_pool = launch(cfg, params, cold_pool, tables([(g2, g2), idle]),
                              [(0, st, ids[st:min(st + 16, 40)], P.RAGGED_PREFILL)])
    cold, _ = launch(cfg, params, cold_pool, tables([(g2, g2), idle]),
                     [(0, 40, tail, P.RAGGED_PREFILL)])
    np.testing.assert_array_equal(hit[0], cold[0])
    assert close(hit[0], ref_logits(cfg, SEED, ids[:40] + tail)[40:]) < TOL


def test_a_stack_of_one_kind_is_one_group_and_refusals_name_the_family():
    one_kind = CFG.replace(name="all-global", rope_local_theta=None,
                           layer_types=("full_attention",) * 4)
    assert one_kind.kv_groups == ("global",)
    assert set(P.init_pool(one_kind, 8, 8)) == {"k", "v", "routed"}
    with pytest.raises(ValueError, match="full_attention"):
        CFG.replace(layer_types=("sliding_attention",) * 4)
    with pytest.raises(ValueError, match="mimo_v2"):
        get_model_config("test-llama-tiny").replace(window_sink=True)
    with pytest.raises(ValueError, match="HOLDS|afmoe / mimo_v2"):
        get_model_config("test-lfm2-tiny").replace(expert_lo=1, n_experts_held=2)
    with pytest.raises(ValueError, match="grouped by layer kind.*shadow"):
        P.refuse_unsupported_latent(CFG, kv_shadow=True)
    with pytest.raises(ValueError, match="speculative"):
        P.refuse_unsupported_latent(CFG, spec=True)
    with pytest.raises(ValueError, match="mesh"):
        P.refuse_unsupported_latent(CFG, mesh=True)
    assert mimo_v2.kind_layers(CFG, "window") == (1, 2)
