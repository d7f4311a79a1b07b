"""The lfm2 family (ISSUE 34: gated short convolutions beside head-dim-64
attention over routed experts, models/lfm2.py) against the plain reference
(cellbench/reference/conv_hybrid_moe.py) at `test-lfm2-tiny`, seeded random
weights, float32, on the CPU. LOGITS are compared, never tokens alone.

Tolerances. TIGHT = 2e-5 absolute on logits whose spread is 0.16: the
program and the reference sum the same float32 products in different orders
(a blocked online softmax, a grouped expert product, a shifted convolution),
which reads 1e-6 here; twenty times that is still two thousand times under
the smallest fault these tests plant (a wrong or missing state reads over
1e-2, asserted as MUTANT).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models import experts, lfm2, stack
from distributed_llm_inference_tpu.models.registry import get_model_config

from lfm2_util import launch, ref_logits

SEED, BS = 5, 16
TIGHT, MUTANT = 2e-5, 1e-2


def ids_of(n, salt=0):
    return [int(t) for t in np.random.default_rng(100 * salt + n).integers(3, 250, n)]


@pytest.fixture(scope="module")
def model():
    cfg = get_model_config("test-lfm2-tiny")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def doc():
    ids = ids_of(60)
    return ids, ref_logits(get_model_config("test-lfm2-tiny"), SEED, ids)


def fresh_pool(cfg, slots=3, blocks=16):
    return P.init_pool(cfg, blocks, BS, n_slots=slots)


def table_of(rows, width=6):
    t = np.zeros((len(rows), width), np.int32)
    for b, blocks in enumerate(rows):
        t[b, :len(blocks)] = blocks
    return t


def err(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# -- the pattern is data -------------------------------------------------------


def test_the_published_pattern_and_the_cut_count_their_layers():
    big = get_model_config("lfm2-24b-a2b")
    assert lfm2.stack_depths(big) == {"conv": 30, "attn": 10, "dense": 2, "moe": 38}
    assert big.layer_types[:3] == ("conv", "conv", "full_attention")
    assert big.layer_types[-2:] == ("full_attention", "conv")
    assert (big.head_dim, big.kv_pack, big.router_score, big.conv_kernel) == (64, 2, "sigmoid", 3)
    kinds = ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2  # a JSON override's list
    cut = big.replace(n_layers=9, first_k_dense=1, layer_types=kinds)
    assert lfm2.stack_depths(cut) == {"conv": 7, "attn": 2, "dense": 1, "moe": 8}
    assert isinstance(cut.layer_types, tuple) and hash(cut) is not None
    shapes = jax.eval_shape(lambda: M.init_params(cut.replace(dtype="bfloat16"),
                                                  jax.random.PRNGKey(0)))
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert 10.3e9 < n_bytes < 10.7e9  # the cell's weights
    pool = jax.eval_shape(lambda: P.init_pool(cut, 3500, 128, n_slots=16))
    assert pool["k"].shape == (2, 3500, 4, 128, 128)  # attention layers only, heads in pairs
    assert pool["conv"].shape == (7, 16, 2, 2048) and pool["tail"].shape == (7, 3500, 2, 2048)
    assert pool["routed"].shape == (2, 8, 64)


@pytest.mark.parametrize("bad", [
    dict(layer_types=("conv",) * 6),  # no layer owns K/V
    dict(layer_types=("conv", "full_attention")),  # not n_layers entries
    dict(layer_types=("conv", "mamba", "conv", "conv", "full_attention", "conv")),
    dict(conv_kernel=1), dict(first_k_dense=6), dict(moe_ffn_dim=0),
])
def test_a_pattern_the_family_cannot_build_is_refused(bad):
    with pytest.raises(ValueError):
        get_model_config("test-lfm2-tiny").replace(**bad)


def test_layer_types_belong_to_the_family():
    with pytest.raises(ValueError, match="lfm2"):
        ModelConfig(arch="llama", layer_types=("conv",) * 22)


# -- the whole forward, dense cache ---------------------------------------------


def test_full_forward_equals_the_reference(model, doc):
    cfg, params = model
    ids, ref = doc
    got, _ = M.forward(cfg, params, jnp.asarray([ids]), M.init_kv_cache(cfg, 1, 64), 0)
    assert ref.std() > 0.1 and err(got[0], ref) < TIGHT


def test_dense_cache_chunks_carry_the_state(model, doc):
    cfg, params = model
    ids, ref = doc
    cache, out = M.init_kv_cache(cfg, 1, 64), []
    for a, b in ((0, 1), (1, 2), (2, 19), (19, 60)):
        lg, cache = M.forward(cfg, params, jnp.asarray([ids[a:b]]), cache, a)
        out.append(lg[0])
    assert err(jnp.concatenate(out), ref) < TIGHT


def test_left_padding_and_meshes_are_refused(model):
    cfg, params = model
    x = jnp.zeros((1, 4, cfg.dim))
    cache = M.init_kv_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="left-padded"):
        M.forward_layers(cfg, params["layers"], x, cache, 0, valid_start=jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="not sharded"):
        M.forward_layers(cfg, params["layers"], x, cache, 0, tp_axis="tp")


# -- the paged pool: state a slot, a tail a block --------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_pool_equals_the_reference(model, doc, impl):
    cfg, params = model
    cfg = cfg.replace(attn_impl=impl)
    ids, ref = doc
    pool, table = fresh_pool(cfg), table_of([[], [3, 4, 5, 6], []])
    out, pool = launch(cfg, params, pool, table, [(1, 0, ids[:40], P.RAGGED_FIRST)])
    rows = [out[0]]
    step = jax.jit(lambda params, tok, pool, pos, act: P._forward_step_paged(
        cfg, params, tok, P._routed_reset(pool), jnp.asarray(table), pos, active=act))
    act = jnp.asarray([False, True, False])
    for t in range(40, 60):
        lg, pool = step(params, jnp.asarray([[0], [ids[t]], [0]]), pool,
                        jnp.asarray([0, t, 0]), act)
        rows.append(np.asarray(lg[1:2]))
    assert err(np.concatenate(rows), ref) < TIGHT
    # rows that are not live left their state alone
    assert not np.asarray(pool["conv"][:, 0]).any() and not np.asarray(pool["conv"][:, 2]).any()
    assert np.asarray(pool["conv"][:, 1]).any()


@pytest.mark.parametrize("offset", range(6))
def test_a_chunk_boundary_at_every_offset_equals_one_piece(model, doc, offset):
    """The boundary falls 0..5 tokens into the row's second query tile: 0, 1
    and 2 tokens of the convolution's reach lie on either side."""
    cfg, params = model
    ids, ref = doc
    cut = 8 + offset
    pool, table = fresh_pool(cfg), table_of([[], [3, 4, 5, 6], []])
    a, pool = launch(cfg, params, pool, table, [(1, 0, ids[:cut], P.RAGGED_FIRST)])
    b, pool = launch(cfg, params, pool, table, [(1, cut, ids[cut:cut + 1], P.RAGGED_PREFILL)])
    c, pool = launch(cfg, params, pool, table, [(1, cut + 1, ids[cut + 1:50], P.RAGGED_PREFILL)])
    assert err(np.concatenate([a[0], b[0], c[0]]), ref[:50]) < TIGHT


def test_a_mixed_launch_of_unequal_rows_keeps_the_rows_apart(model, doc):
    cfg, params = model
    ids, ref = doc
    others = [ids_of(n, salt=7) for n in (23, 9)]
    refs = [ref_logits(cfg, SEED, o) for o in others]
    pool = fresh_pool(cfg)
    table = table_of([[1, 2], [3, 4, 5, 6], [7]])
    got = {0: [], 1: [], 2: []}
    plan = [  # (row, first, n) of each launch: rows start, go on and end at different times
        [(1, 0, 19), (0, 0, 5)],
        [(0, 5, 1), (1, 19, 14), (2, 0, 3)],
        [(2, 3, 6), (1, 33, 17), (0, 6, 17)],
    ]
    seqs = {0: others[0], 1: ids, 2: others[1]}
    for entries in plan:
        out, pool = launch(cfg, params, pool, table, [
            (r, s, seqs[r][s:s + n], P.RAGGED_FIRST if s == 0 else P.RAGGED_PREFILL)
            for r, s, n in entries])
        for (r, _, _), lg in zip(entries, out):
            got[r].append(lg)
    assert err(np.concatenate(got[1]), ref[:50]) < TIGHT
    assert err(np.concatenate(got[0]), refs[0]) < TIGHT
    assert err(np.concatenate(got[2]), refs[1]) < TIGHT


def _prefilled(cfg, params, rows):
    """A pool with each row's prompt landed, and the slots armed to decode."""
    pool = fresh_pool(cfg, slots=len(rows))
    table = table_of([[1 + 4 * b + j for j in range(4)] for b in range(len(rows))])
    first = []
    for b, ids in enumerate(rows):
        out, pool = launch(cfg, params, pool, table, [(b, 0, ids, P.RAGGED_FIRST)])
        first.append(int(out[0][-1].argmax()))
    state, sparams = G.init_slots(len(rows), cfg.vocab_size)
    state = state._replace(
        token=jnp.asarray(first, jnp.int32),
        pos=jnp.asarray([len(r) for r in rows], jnp.int32),
        active=jnp.ones((len(rows),), bool),
        remaining=jnp.asarray([40, 9], jnp.int32)[:len(rows)],
    )
    return pool, jnp.asarray(table), state, sparams, first


def test_the_sixteen_step_decode_chunk_equals_sixteen_single_steps(model):
    """Rows at 14 and 27 tokens: both cross a block's end inside the chunk
    (tails written by decode), and the second row's budget ends at step 9:
    a dead row leaves state and tails alone."""
    cfg, params = model
    rows = [ids_of(14, 1), ids_of(27, 2)]
    key = jax.random.PRNGKey(0)
    pool, table, state, sparams, first = _prefilled(cfg, params, rows)
    em16, mask16, st16, pool16, _ = P.decode_slots_paged(
        cfg, params, state, pool, table, key, sparams, num_steps=16)
    pool, table, state, sparams, _ = _prefilled(cfg, params, rows)
    em1 = []
    for _ in range(16):
        e, m, state, pool, _ = P.decode_slots_paged(
            cfg, params, state, pool, table, key, sparams, num_steps=1)
        em1.append(np.asarray(e[0]) * np.asarray(m[0]))
    assert (np.asarray(em16) * np.asarray(mask16) == np.stack(em1)).all()
    assert int(np.asarray(mask16)[:, 1].sum()) == 9 and np.asarray(mask16)[:, 0].all()
    for name in ("conv", "tail"):
        assert err(pool16[name], pool[name]) < TIGHT, name  # (two compiles' roundings)
    for leaf in ("k", "v"):  # (the trash block takes dead rows' writes)
        assert err(pool16[leaf][:, 1:], pool[leaf][:, 1:]) < TIGHT
    # and the chunk's tokens are the reference's own choices
    for b, ids in enumerate(rows):
        n = int(np.asarray(mask16)[:, b].sum())
        gen = [first[b]] + [int(t) for t in np.asarray(em16)[:n, b]]
        ref = ref_logits(cfg, SEED, ids + gen)
        assert [int(r.argmax()) for r in ref[len(ids) - 1:len(ids) + n]] == gen
    # the blocks that decode filled carry the tail a cold prefill of the
    # same tokens leaves: a later prefix hit on them would be exact
    ids = rows[0] + [first[0]] + [int(t) for t in np.asarray(em16)[:16, 0]]
    cold, t2 = fresh_pool(cfg, slots=2), table_of([[9, 10], []])
    _, cold = launch(cfg, params, cold, t2, [(0, 0, ids[:16], P.RAGGED_FIRST)])
    assert err(cold["tail"][:, 9], pool16["tail"][:, 1]) < TIGHT
    assert np.abs(np.asarray(pool16["tail"][:, 1])).max() > 1e-3


def test_a_slot_let_again_starts_from_zeros_whatever_its_state_holds(model, doc):
    """The old tenant's last launch may still be in flight when the slot is
    let: the new tenant's first chunk never reads the slot's live state."""
    cfg, params = model
    ids, ref = doc
    pool, table = fresh_pool(cfg), table_of([[1, 2, 3], [], []])
    _, pool = launch(cfg, params, pool, table, [(0, 0, ids_of(33, 3), P.RAGGED_FIRST)])
    assert np.abs(np.asarray(pool["conv"][:, 0])).max() > 1e-3  # the old tenant's
    table = table_of([[4, 5, 6], [], []])
    out, pool = launch(cfg, params, pool, table, [(0, 0, ids[:30], P.RAGGED_FIRST)])
    assert err(out[0], ref[:30]) < TIGHT


# -- a prefix hit restores the state --------------------------------------------


def _hit(cfg, params, ids, depth, retrace=False):
    """A first tenant prefills `ids` in row 1; row 2 then maps its first
    `depth` blocks and prefills the rest as its first chunk."""
    pool = fresh_pool(cfg)
    table = table_of([[], [3, 4, 5, 6], []])
    _, pool = launch(cfg, params, pool, table, [(1, 0, ids[:41], P.RAGGED_FIRST)])
    _, pool = launch(cfg, params, pool, table, [(1, 41, ids[41:], P.RAGGED_PREFILL)])
    p0 = depth * BS
    table[2, :depth] = table[1, :depth]
    table[2, depth:4] = [9, 10, 11, 12][:4 - depth]
    table[1] = 0  # the first tenant has gone; the blocks stay (the index holds them)
    out, pool = launch(cfg, params, pool, table, [(2, p0, ids[p0:], P.RAGGED_FIRST)],
                       retrace=retrace)
    return out[0]


@pytest.mark.parametrize("depth", [1, 3])
def test_a_prefix_hit_equals_a_cold_prefill(model, doc, depth):
    cfg, params = model
    ids, ref = doc
    got = _hit(cfg, params, ids, depth)
    cold, _ = launch(cfg, params, fresh_pool(cfg), table_of([[], [], [9, 10, 11, 12]]),
                     [(2, 0, ids, P.RAGGED_FIRST)])
    assert err(got, cold[0][depth * BS:]) < TIGHT
    assert err(got, ref[depth * BS:]) < TIGHT


def _rows_mutant(change):
    """engine/paged._ragged_rows with its answer changed."""
    original = P._ragged_rows

    def rows(table, meta, tok_row):
        return change(original(table, meta, tok_row))

    return rows


def _neighbour(tok_row, j):
    return jnp.arange(tok_row.shape[0]) >= j  # the flat index decides


MUTANTS = {
    "tail_not_restored": (P, "_ragged_rows", _rows_mutant(
        lambda r: r._replace(start=jnp.zeros_like(r.start)))),
    "tail_of_the_wrong_block": (P, "_ragged_rows", _rows_mutant(
        lambda r: r._replace(start=jnp.where(r.start > 0, r.start - BS, 0)))),
    "state_not_zeroed_at_a_re_let": (P, "_ragged_rows", _rows_mutant(
        lambda r: r._replace(fresh=jnp.zeros_like(r.fresh)))),
    "a_neighbour_rows_z_read_across_the_row_boundary": (lfm2, "_same_row", _neighbour),
}


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_a_mutant_fails_by_over_a_hundredth(model, doc, monkeypatch, mutant):
    cfg, params = model
    ids, ref = doc
    monkeypatch.setattr(*MUTANTS[mutant])
    if mutant.startswith("tail"):
        got, want = _hit(cfg, params, ids, 3, retrace=True), ref[3 * BS:]
    elif mutant.startswith("state"):
        pool, table = fresh_pool(cfg), table_of([[1, 2, 3], [], []])
        _, pool = launch(cfg, params, pool, table, [(0, 0, ids_of(33, 3), P.RAGGED_FIRST)])
        out, _ = launch(cfg, params, pool, table_of([[4, 5, 6], [], []]),
                        [(0, 0, ids[:30], P.RAGGED_FIRST)], retrace=True)
        got, want = out[0], ref[:30]
    else:
        # two rows side by side in one launch: the second row's first tokens
        # sit right behind the first row's last (a whole tile of 8)
        pool, table = fresh_pool(cfg), table_of([[1, 2], [3, 4, 5, 6], []])
        out, _ = launch(cfg, params, pool, table, [
            (0, 0, ids_of(8, 9), P.RAGGED_FIRST), (1, 0, ids[:20], P.RAGGED_FIRST)],
            retrace=True)
        got, want = out[1], ref[:20]
    assert err(got, want) > MUTANT, mutant


# -- head dim 64 in the paged kernels --------------------------------------------


def test_packed_heads_score_and_sum_as_the_heads_themselves():
    rng = np.random.default_rng(0)
    B, T, H, KV, Dh, S = 2, 3, 8, 4, 64, 10
    q = jnp.asarray(rng.normal(size=(B, T, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, Dh)), jnp.float32)
    qp, kp, vp, part = stack.pack_heads(q, k, v, 2)
    assert qp.shape == (B, T, H, 128) and kp.shape == (B, S, KV // 2, 128)
    group = H // KV
    plain = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, group, axis=2))
    packed = jnp.einsum("bthd,bshd->bhts", qp, jnp.repeat(kp, 2 * group, axis=2))
    assert err(plain, packed) < 1e-4  # the zero lanes add nothing (scores of 64 products)
    p = jax.nn.softmax(plain, axis=-1)
    want = jnp.einsum("bhts,bshd->bthd", p, jnp.repeat(v, group, axis=2))
    wide = jnp.einsum("bhts,bshd->bthd", p, jnp.repeat(vp, 2 * group, axis=2))
    assert err(stack.unpack_heads(wide, part, 2), want) < 1e-5
    assert float(jnp.abs(want).max()) > 0.5


def test_both_paged_kernels_write_the_packed_pool_in_place_like_the_xla_twin(model, doc):
    """The ragged kernel (prefill, two rows) and the decode kernel, interpreted,
    against XLA's scatter-and-gather twin: logits and every pool leaf, the
    kernels' own writes included (the trash block aside)."""
    from distributed_llm_inference_tpu.ops.paged_attention import writes_in_place

    cfg, params = model
    ids, _ = doc
    pools = {}
    for impl in ("xla", "pallas"):
        c = cfg.replace(attn_impl=impl)
        pool, table = fresh_pool(c), table_of([[1, 2], [3, 4, 5, 6], []])
        assert writes_in_place(pool["k"]) and pool["k"].shape[-1] == 128
        a, pool = launch(c, params, pool, table, [
            (1, 0, ids[:37], P.RAGGED_FIRST), (0, 0, ids[40:51], P.RAGGED_FIRST)])
        lg, pool = P._forward_step_paged(
            c, params, jnp.asarray([[ids[51]], [ids[37]], [0]]), P._routed_reset(pool),
            jnp.asarray(table), jnp.asarray([11, 37, 0]), active=jnp.asarray([True, True, False]))
        pools[impl] = (np.concatenate(a), np.asarray(lg[:2]), pool)
    (ax, lx, px), (ap, lp_, pp) = pools["xla"], pools["pallas"]
    assert err(ax, ap) < TIGHT and err(lx, lp_) < TIGHT
    for leaf in ("k", "v"):
        assert err(px[leaf][:, 1:], pp[leaf][:, 1:]) < TIGHT, leaf
        assert np.abs(np.asarray(pp[leaf][:, 1:7])).max() > 0.1
    for leaf in ("conv", "tail"):
        assert err(px[leaf], pp[leaf]) < TIGHT, leaf


# -- the router ------------------------------------------------------------------


def test_the_bias_moves_the_choice_and_not_the_weights():
    cfg = get_model_config("test-lfm2-tiny")
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(32, cfg.dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(cfg.dim, cfg.n_experts)) * cfg.dim ** -0.5, jnp.float32)
    zero = jnp.zeros((cfg.n_experts,), jnp.float32)
    chosen0, weights0 = experts.route(cfg, h, w, zero)
    s = np.asarray(jax.nn.sigmoid(h @ w))
    top = np.sort(np.argsort(-s, axis=-1)[:, :2], axis=-1)
    assert (np.sort(np.asarray(chosen0), axis=-1) == top).all()
    picked = np.take_along_axis(s, np.asarray(chosen0), axis=-1)
    want = picked / (picked.sum(-1, keepdims=True) + 1e-6)  # norm_topk_prob, the family's 1e-6
    assert err(weights0, want) < 1e-6 and abs(float(weights0.sum(-1).mean()) - 1) < 1e-5
    # a bias that lifts expert 5 over everything: chosen everywhere, weighed by its own score
    bias = zero.at[5].set(10.0)
    chosen, weights = experts.route(cfg, h, w, bias)
    assert (np.asarray(chosen) == 5).any(axis=-1).all()
    assert not (np.asarray(chosen0) == 5).any(axis=-1).all()
    picked = np.take_along_axis(s, np.asarray(chosen), axis=-1)
    assert err(weights, picked / (picked.sum(-1, keepdims=True) + 1e-6)) < 1e-6
    # without norm_topk_prob the scores weigh as they are, times the scaling
    raw = cfg.replace(moe_renormalize=False, routed_scaling=2.0)
    _, weights = experts.route(raw, h, w, bias)
    assert err(weights, 2.0 * picked) < 1e-6


def test_router_score_is_a_field_with_the_familys_default():
    assert get_model_config("test-lfm2-tiny").router_score == "sigmoid"
    assert get_model_config("test-mla-moe-tiny").router_score == "sigmoid"
    assert get_model_config("test-sdar-tiny").router_score == "softmax"
    assert get_model_config("test-sdar-tiny").replace(router_score="sigmoid").router_score == "sigmoid"
    assert get_model_config("test-mla-moe-tiny").router_norm_eps == 0.0
    with pytest.raises(ValueError, match="router_score"):
        get_model_config("test-sdar-tiny").replace(router_score="tanh")
