"""The operators ISSUE 48 adds, each against a plain form of itself at tiny
sizes on the CPU: the chunked decayed linear-attention scan against the
token-by-token recurrence; the block selection against the reference's
(cellbench/reference/sparse_linear_hybrid.py), tie rule included; the
scoring's kernel (ISSUE 50) against the XLA form it replaced, kept here, over
launches of the engine's shapes; the paged walk over a page LIST against the
range walk (a full list: bit for bit) and against the gather path under a
selection a query.
"""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.models import minicpm_sala as MS
from distributed_llm_inference_tpu.models.registry import get_model_config
from distributed_llm_inference_tpu.ops.linear_attention import (
    decay_slopes, linear_attend_rows,
)
from distributed_llm_inference_tpu.ops.paged_attention import (
    paged_flash_attend, ragged_paged_attend,
)
from distributed_llm_inference_tpu.ops.sparse_select import select_blocks

from paged_walk_cases import (
    LISTED_CASES, LISTED_DECODE_CASES, check_listed_case,
    check_listed_decode_case,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "cellbench"))

from harness import manifest  # noqa: E402

REF = manifest.load_module("reference", "sparse_linear_hybrid")
CFG = get_model_config("test-sala-tiny")


# -- the scan -------------------------------------------------------------------

def _recurrence(q, k, v, S):
    """One row's tokens q, k, v [n, H, Dh] from the state S [H, Dh, Dh],
    one at a time in float64: S = a S + k^T v, o = q S."""
    a = np.exp(-np.asarray(decay_slopes(q.shape[1]), np.float64))
    q, k, v, S = (np.asarray(x, np.float64) for x in (q, k, v, S))
    out = []
    for t in range(q.shape[0]):
        S = a[:, None, None] * S + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hd,hde->he", q[t], S))
    return np.stack(out), S


# a launch's flat axis: (tile, [(fleet row or -1 for a tile of launch padding,
# its tokens)]), 4 fleet rows, row 1 never carrying a token
LAUNCHES = {
    # a long chunk, a decode token, a dead tile between, a row with no token
    "tq1": (1, [(0, 12), (-1, 0), (2, 1), (3, 8)]),
    "tq4": (4, [(0, 12), (-1, 0), (2, 4), (3, 8)]),
    # a row whose tokens span 25 tiles (two blocks of the program's 128, the
    # second ending with the flat axis) beside rows of one token
    "tiles-of-8": (8, [(2, 1), (0, 200), (-1, 0), (3, 1)]),
    # a chunk of two tokens: shorter than a tile, longer than the recurrence
    "short-chunk": (8, [(3, 2), (0, 1)]),
    # the decode chunk's form: one token a row, row w's at w, row 1 not active
    "decode-rows": (1, [(0, 1), (-1, 0), (2, 1), (3, 1)]),
    "no-token": (8, [(-1, 0), (-1, 0)]),
}


@pytest.mark.parametrize("launch", list(LAUNCHES))
def test_the_chunked_scan_is_the_recurrence(launch):
    """Rows side by side on a launch's flat axis, each carrying on from its
    own state: outputs and states equal the recurrence's, a dead token reads
    zeros, and a row with no token of the launch keeps its state bit for
    bit (the leaf is the scan's aliased output)."""
    rng = np.random.default_rng(0)
    H, Dh, R = 3, 8, 4
    tq, order = LAUNCHES[launch]
    tok_row, spans = [], {}
    for r, n in order:
        width = -(-max(n, 1) // tq) * tq
        if r >= 0:
            spans[r] = (len(tok_row), n)
        tok_row += [r] * n + [-1] * (width - n)
    tok_row = np.asarray(tok_row, np.int32)
    W = len(tok_row)
    q, k, v = (jnp.asarray(rng.normal(size=(W, H, Dh)), jnp.float32)
               for _ in range(3))
    state = np.asarray(rng.normal(size=(R, H, Dh, Dh)), np.float32)
    o, new = linear_attend_rows(q, k, v, jnp.asarray(state),
                                jnp.asarray(tok_row), tq)
    for r, (a, n) in spans.items():
        want_o, want_S = _recurrence(q[a:a + n], k[a:a + n], v[a:a + n],
                                     state[r])
        np.testing.assert_allclose(o[a:a + n], want_o, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(new[r], want_S, rtol=2e-5, atol=2e-5)
    for r in set(range(R)) - set(spans):  # no token: untouched
        np.testing.assert_array_equal(new[r], state[r])
    assert 1 not in spans and not np.any(np.asarray(o)[tok_row < 0])


def _linear_layer(rows, pool):
    """`MS.linear_attention` of the tiny preset's first linear layer over
    a launch of 16 flat tokens in tiles of 8 (row 1's chunk, row 0's
    decode token)."""
    from distributed_llm_inference_tpu.ops.rope import rope_cos_sin

    params = MS.init_params(CFG, jax.random.PRNGKey(3))
    lp = {name: leaf[0] for name, leaf in params["layers"]["linear"].items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (16, 1, CFG.dim), jnp.float32)
    pos = jnp.arange(16, dtype=jnp.int32)
    cos, sin = rope_cos_sin(pos[:, None], CFG.head_dim, CFG.rope_theta)
    ctx = types.SimpleNamespace(rows=rows, pos=pos, cos=cos, sin=sin, tile=8)
    return MS.linear_attention(CFG, ctx, lp, h.astype(CFG.jnp_dtype), pool, 0)


def test_a_fresh_row_starts_from_its_snapshot_and_a_taken_one_holds_the_state():
    """Around the scan (`models/minicpm_sala.linear_attention`): a fresh row
    with a snapshot to restore reads and leaves what a row carrying that
    state on does; a fresh row without one starts from zeros; a row with
    `take` leaves its state after the launch in that snapshot; the other
    snapshots and the row with no token stay bit for bit."""
    from distributed_llm_inference_tpu.engine.paged import StateRows

    rng = np.random.default_rng(5)
    R, N = 3, 4
    shape = (CFG.linear_heads, CFG.head_dim, CFG.head_dim)
    n_lin = len(CFG.linear_layers)
    lin = np.asarray(rng.normal(size=(R,) + shape), np.float32)
    snap = np.asarray(rng.normal(size=(N,) + shape), np.float32)
    tok_row = np.full((16,), -1, np.int32)
    tok_row[:5], tok_row[8] = 1, 0

    def run(lin, fresh, restore, take):
        pool = {"lin": (jnp.asarray(lin),) * n_lin,
                "snap": (jnp.asarray(snap),) * n_lin}
        rows = StateRows(
            jnp.asarray(tok_row), jnp.zeros((R, 2), jnp.int32),
            jnp.asarray(fresh), jnp.zeros((R,), jnp.int32),
            jnp.asarray(restore, jnp.int32), jnp.asarray(take, jnp.int32))
        out, pool = _linear_layer(rows, pool)
        return (np.asarray(out, np.float32), np.asarray(pool["lin"][0]),
                np.asarray(pool["snap"][0]))

    none = [-1] * R
    # row 1 restores snapshot 2 and keeps its state in snapshot 0; row 0
    # starts from zeros
    out, new, kept = run(lin, [True, True, False], [-1, 2, -1], [-1, 0, -1])
    carried = lin.copy()
    carried[0], carried[1] = 0.0, snap[2]
    want_out, want_new, same = run(carried, [False] * R, none, none)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new, want_new, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(new[2], lin[2])
    np.testing.assert_array_equal(kept[0], new[1])
    np.testing.assert_array_equal(kept[1:], snap[1:])
    np.testing.assert_array_equal(same, snap)


def test_a_row_that_is_not_active_keeps_its_state_and_the_slopes_are_the_familys():
    s = np.asarray(decay_slopes(32))
    np.testing.assert_allclose(s[[0, 31]], [2 ** -0.25, 2 ** -8.0], rtol=1e-6)
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 3, 8)), jnp.float32)
               for _ in range(3))
    S = jnp.asarray(rng.normal(size=(2, 3, 8, 8)), jnp.float32)
    # a decode step: one token a row, row 1 not active
    o, new = linear_attend_rows(q, k, v, S, jnp.asarray([0, -1]), 1)
    np.testing.assert_array_equal(new[1], S[1])
    assert not np.any(np.asarray(o[1])) and np.any(np.asarray(new[0] != S[0]))


# -- the selection --------------------------------------------------------------

def _keys_by_block(cfg, k):
    """Compressed keys of one row's keys k [T, KV, Dh] as the pool holds
    them: [KV, MB x slots, Dh], the key that ends at token e at row
    e // stride (block e // bs, slot (e % bs) // stride)."""
    T = k.shape[0]
    st, kn = cfg.sparse_stride, cfg.sparse_kernel
    ck = np.zeros((k.shape[1], T // st, k.shape[2]), np.float32)
    for e in range(kn - 1, T, st):
        ck[:, e // st] = np.mean(np.asarray(k)[e - kn + 1:e + 1], axis=0)
    return jnp.asarray(ck)


def _ref_selection(cfg, q, k, t):
    sel = dict(kernel=cfg.sparse_kernel, stride=cfg.sparse_stride,
               block=cfg.sparse_block, topk=cfg.sparse_topk,
               window=cfg.sparse_window, init=cfg.sparse_init_blocks,
               dense_len=cfg.sparse_dense_len)
    T, st, kn = k.shape[0], cfg.sparse_stride, cfg.sparse_kernel
    m = jnp.mean(k.reshape(T // st, st, *k.shape[1:]), axis=1)
    J = T // st - kn // st + 1
    c = sum(m[i:i + J] for i in range(kn // st)) / (kn // st)
    return REF.chosen_blocks(q, c, t, KV=cfg.n_kv_heads, Dh=cfg.head_dim,
                             n_blocks=T // cfg.sparse_block, **sel)


def _xla_block_scores(cfg, q, ck, pos):
    """The block scores as `select_blocks` computed them in XLA before
    ISSUE 50 gave them a kernel: q [G, tq, KV, group, Dh], ck
    [G, KV, MB x slots, Dh] each tile's row's compressed keys gathered by
    block and slot, pos [G, tq] -> [G, tq, KV, MB] float32."""
    F32 = jnp.float32
    G, tq, KV = q.shape[:3]
    bs, stride, kernel = cfg.sparse_block, cfg.sparse_stride, cfg.sparse_kernel
    slots = bs // stride
    MB = ck.shape[2] // slots
    blocks = jnp.arange(MB, dtype=jnp.int32)
    end = (blocks[:, None] * bs
           + jnp.arange(slots, dtype=jnp.int32)[None, :] * stride
           + stride - 1)  # [MB, slots]
    valid = (end <= pos[..., None, None]) & (end >= kernel - 1)
    J = MB * slots
    ok = valid.reshape(G, tq, 1, 1, J)
    s = jnp.einsum("gtkhd,gkjd->gtkhj", q, ck.astype(q.dtype),
                   preferred_element_type=F32) * cfg.head_dim ** -0.5
    s = jnp.where(ok, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    r = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=3)  # [G,tq,KV,J]
    r = jnp.where(ok[:, :, 0], r, -jnp.inf).reshape(G, tq, KV, MB, slots)
    score = jnp.max(r, axis=4)
    over = (kernel - 1) // stride
    if over:
        nxt = jnp.max(r[..., :over], axis=4)
        nxt = jnp.concatenate(
            [nxt[..., 1:], jnp.full_like(nxt[..., :1], -jnp.inf)], axis=3)
        score = jnp.maximum(score, nxt)
    return score


def _xla_select_blocks(cfg, score, pos):
    """Which blocks each query reads, as the model chose them in XLA before
    ISSUE 50: score [G, tq, KV, MB] float32 block scores, pos [G, tq] ->
    chosen [G, tq, KV, MB] bool (every block up to the query's own where
    fewer than cfg.sparse_dense_len positions are visible)."""
    bs, MB = cfg.sparse_block, score.shape[3]
    blocks = jnp.arange(MB, dtype=jnp.int32)
    visible = blocks <= (pos // bs)[..., None]  # [G, tq, MB]
    forced = (blocks < cfg.sparse_init_blocks) | (
        blocks >= (jnp.maximum(pos - (cfg.sparse_window - 1), 0)
                   // bs)[..., None])
    score = jnp.where(forced[:, :, None], jnp.inf, score)
    score = jnp.where(visible[:, :, None], score, -jnp.inf)
    # -inf scores (blocks past the query) may fill the k where few blocks
    # are visible: `visible` cuts them again
    picked = _xla_top_mask(score, min(cfg.sparse_topk, MB))  # [G, tq, KV, MB]
    dense = (pos + 1 < cfg.sparse_dense_len)[..., None, None]
    return jnp.where(dense, True, picked) & visible[:, :, None]


def _xla_top_mask(score, k: int):
    """The k largest of score [..., n] float32 along its last axis as a bool
    mask, equal scores to the lower index: what `lax.top_k` picks, without
    its sort (0.8 ms a layer of a mixed step on the chip). The k-th largest
    value is found bit by bit on the floats' order-preserving integer keys
    (32 counts of "how many are at least this"); everything above it is in,
    and of its equals the first few by index."""
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    # a float's bits as an unsigned key of the same order (-inf lowest)
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31)), jnp.uint32)

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), jnp.uint32(31) - i.astype(
            jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, t)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(score.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    equal = key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


@pytest.mark.parametrize("tie", [False, True], ids=["random", "ties"])
def test_the_selection_is_the_references(tie):
    """Every query of a 96-token row (12 blocks of 8; the first 23 below
    the tiny dense length): the blocks `select_blocks` picks from the block
    scores over the pool's compressed keys are the reference's. `ties`:
    every key the same, so every block scores alike and the lower block
    wins, in both."""
    cfg, T = CFG, 96
    rng = np.random.default_rng(2)
    KV, g, Dh = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.normal(size=(T, KV, g, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, KV, Dh)), jnp.float32)
    if tie:
        k = jnp.broadcast_to(k[:1], k.shape)
    t = jnp.arange(T, dtype=jnp.int32)
    want = np.asarray(_ref_selection(cfg, q, k, t))
    tq = 8
    ck = jnp.broadcast_to(_keys_by_block(cfg, k)[None],
                          (T // tq,) + _keys_by_block(cfg, k).shape)
    pos = t.reshape(T // tq, tq)
    got = np.asarray(_xla_select_blocks(cfg, _xla_block_scores(
        cfg, q.reshape(T // tq, tq, KV, g, Dh), ck, pos), pos))
    got = got.reshape(T, KV, -1)
    np.testing.assert_array_equal(got, want)
    reads = got.sum(-1)
    assert np.all(reads[:cfg.sparse_dense_len - 1] ==
                  (t[:cfg.sparse_dense_len - 1] // 8 + 1)[:, None])
    assert np.all(reads[cfg.sparse_dense_len - 1:] == np.minimum(
        t[cfg.sparse_dense_len - 1:] // 8 + 1, cfg.sparse_topk)[:, None])
    if tie:  # block 0, the window's block (88-95), the two lowest others
        np.testing.assert_array_equal(np.flatnonzero(got[95, 0]), [0, 1, 2, 11])
        np.testing.assert_array_equal(np.flatnonzero(got[92, 1]), [0, 1, 10, 11])


# -- the scoring's kernel ---------------------------------------------------------

# a launch over 4 fleet rows under a table 16 blocks wide (128 positions):
# (tile, [(row, first position, tokens)] in flat order, options)
SCORED = {
    # rows of different lengths side by side: a chunk of three tiles (the
    # last of 4 tokens) between decode tokens, launch padding behind
    "lengths": (8, [(2, 100, 1), (0, 40, 20), (3, 9, 1), (1, 77, 1)], {}),
    # a tile in the middle that carries nothing (a row switched off on the
    # device), between two tiles of ONE row: its keys stay where they are
    "dead-tile": (8, [(1, 48, 24), (3, 60, 1)], {"dead": [1]}),
    # below the dense length (24): every visible block, whatever it scores
    "below-dense": (8, [(0, 3, 8), (2, 22, 1), (1, 0, 1)], {}),
    # a row at the table's full width, its last query at the last position
    "full-width": (8, [(3, 112, 16), (0, 127, 1)], {}),
    # every key alike: every block scores alike, the lower block wins
    "ties": (8, [(0, 80, 16), (1, 95, 1)], {"tie": True}),
    # the chunk's own compressed keys, written by `compressed_keys` in the
    # same launch from the launch's keys and the pool's two blocks behind
    "own-keys": (8, [(2, 37, 27), (1, 64, 1)], {"write": True}),
    # the decode chunk's form: a tile a row, row 1 not active
    "decode-rows": (1, [(0, 101, 1), (1, 50, 1), (2, 30, 1), (3, 127, 1)],
                    {"dead": [1]}),
    # the served dtype: a block's 8 keys and 8 rows of padding a tile
    "bfloat16": (8, [(0, 64, 16), (1, 120, 1)], {"dtype": "bfloat16"}),
}


@pytest.mark.parametrize("case", list(SCORED))
def test_the_kernels_block_scores_are_the_gathered_forms(case):
    """`ops/sparse_select.select_blocks` over the pool's leaf through the
    block table against the XLA form over each tile's row's gathered keys:
    the same scores (-inf in the same places) and the same blocks, block
    for block (the kernel's own choice, and the XLA rules over the kernel's
    scores); in float32 they are the reference's too. Blocks the launch's rows do not hold are NaN in the leaf, and no
    copy brings them; a held block's rows that are no key of the row yet
    (and the leaf's padding rows) hold 1e30, which no score shows."""
    from distributed_llm_inference_tpu.engine import paged as EP

    tq, entries, opt = SCORED[case]
    cfg = CFG.replace(dtype=opt.get("dtype", "float32"))
    dt = cfg.jnp_dtype
    rng = np.random.default_rng(7)
    KV, g, Dh = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    bs, st, kn = cfg.sparse_block, cfg.sparse_stride, cfg.sparse_kernel
    slots, R, MB = bs // st, 4, 16
    T = MB * bs
    N = R * MB + 1
    table = 1 + rng.permutation(N - 1).reshape(R, MB).astype(np.int32)
    ks = rng.normal(size=(R, T, KV, Dh)).astype(np.float32)
    if opt.get("tie"):
        ks[:] = ks[:, :1]
    ks = np.asarray(jnp.asarray(ks, dt).astype(jnp.float32))  # as the pool's
    qs = np.asarray(jnp.asarray(rng.normal(size=(R, T, KV, g, Dh)), dt)
                    .astype(jnp.float32))
    width = -(-sum(-(-n // tq) for _, _, n in entries) // 2) * 2 * tq + tq
    meta, tok_row, tok_pos, _, _ = EP.build_ragged_meta(
        [(r, p, n, EP.RAGGED_PREFILL if n > 1 else EP.RAGGED_DECODE)
         for r, p, n in entries], width=width, tile=tq)
    for g_dead in opt.get("dead", []):
        tok_row[g_dead * tq:(g_dead + 1) * tq] = -1
    live = tok_row >= 0
    # the leaf: a row's keys up to the launch's first token of the row
    # (`write`) or its last
    first = {r: p for r, p, _ in entries}
    upto = {r: (p if opt.get("write") else p + n) for r, p, n in entries}
    RB = EP.init_pool(cfg, 2, bs, n_slots=1)["ck"][0].shape[1]
    assert RB == (16 if dt == jnp.bfloat16 else 8)
    leaf = np.full((N, RB, Dh), np.nan, np.float32)
    leaf[table[list(upto)]] = 1e30
    by_block = {r: np.asarray(_keys_by_block(cfg, jnp.asarray(ks[r])))
                for r in range(R)}  # [KV, MB x slots, Dh]
    for r, n in upto.items():
        for e in range(kn - 1, n, st):
            blk, slot = table[r, e // bs], (e % bs) // st
            leaf[blk, np.arange(KV) * slots + slot] = by_block[r][:, e // st]
    leaf = jnp.asarray(leaf, dt)
    W = len(tok_row)
    rix = np.maximum(tok_row, 0)
    q = jnp.asarray(np.where(live[:, None, None, None],
                             qs[rix, tok_pos], np.nan), dt)
    rows = EP.StateRows(jnp.asarray(tok_row), jnp.asarray(table),
                        jnp.zeros((R,), bool), jnp.zeros((R,), jnp.int32))
    pos = jnp.asarray(tok_pos)
    if opt.get("write"):
        # the pool's K of the positions before the launch; the launch's own
        pool_k = np.zeros((1, N, KV, bs, Dh), np.float32)
        for r, p in first.items():
            for b in range(-(-p // bs)):
                n = min(bs, p - b * bs)
                pool_k[0, table[r, b], :, :n] = ks[r, b * bs:b * bs + n] \
                    .transpose(1, 0, 2)
        new_k = jnp.asarray(np.where(live[:, None, None], ks[rix, tok_pos],
                                     np.nan), dt)
        leaf = MS.compressed_keys(cfg, new_k, jnp.asarray(pool_k, dt), leaf,
                                  0, rows, pos)
    tiles = MS.tile_meta(cfg, rows, pos, tq)
    G = W // tq
    got, chosen = (np.asarray(a).reshape(W, KV, MB) for a in select_blocks(
        q.reshape(G, tq, KV, g, Dh), leaf, rows.table, tiles, block=bs,
        stride=st, kernel=kn, topk=cfg.sparse_topk, window=cfg.sparse_window,
        init=cfg.sparse_init_blocks, dense_len=cfg.sparse_dense_len,
        interpret=True))
    # the gathered form, each tile's row's keys whole
    tile_row = np.maximum(tok_row.reshape(G, tq).max(axis=1), 0)
    gathered = jnp.asarray(np.stack([by_block[r] for r in tile_row]), dt)
    want = np.asarray(_xla_block_scores(
        cfg, jnp.where(live[:, None, None, None], q, 0).reshape(
            G, tq, KV, g, Dh), gathered, pos.reshape(G, tq))
    ).reshape(W, KV, MB)
    assert not np.any(np.isnan(got[live]))
    np.testing.assert_array_equal(np.isneginf(got[live]),
                                  np.isneginf(want[live]))
    np.testing.assert_allclose(
        got[live], want[live], rtol=1e-5 if dt == jnp.float32 else 2e-2)
    dead = np.repeat(~live.reshape(G, tq).any(axis=1), tq)
    assert np.all(np.isneginf(got[dead])) and not np.any(chosen[dead])
    pick = lambda score: np.asarray(_xla_select_blocks(  # noqa: E731
        cfg, jnp.asarray(score).reshape(G, tq, KV, MB),
        pos.reshape(G, tq))).reshape(W, KV, MB)[live]
    chosen = chosen[live]
    np.testing.assert_array_equal(chosen, pick(got))
    if dt == jnp.float32:
        np.testing.assert_array_equal(chosen, pick(want))
        for r in upto:  # the reference, over the row's whole sequence
            mine = live & (tok_row == r)
            ref = np.asarray(_ref_selection(
                cfg, jnp.asarray(qs[r]), jnp.asarray(ks[r]),
                jnp.arange(T, dtype=jnp.int32)))
            np.testing.assert_array_equal(
                chosen[(tok_row == r)[live]], ref[tok_pos[mine]])
    else:  # a near-tie may fall either way at bfloat16 products' rounding
        assert np.mean(chosen == pick(want)) > 0.99
    if opt.get("tie"):  # position 95: block 0, the window's, the two lowest
        np.testing.assert_array_equal(np.flatnonzero(chosen[-1, 0]),
                                      [0, 1, 2, 11])


def test_a_stacks_sparse_layers_trace_the_scoring_kernel_once(monkeypatch):
    """Two sparse layers' calls at one shape inside one program: the kernel's
    body is traced once (`select_blocks` is a `jax.jit` of its own), so a
    step program's start pays one trace and lowering of it, not one a
    layer."""
    from distributed_llm_inference_tpu.ops import sparse_select as SS

    traced = []
    body = SS._select_kernel

    def counted(*refs, **static):
        traced.append(static["tq"])
        return body(*refs, **static)

    monkeypatch.setattr(SS, "_select_kernel", counted)
    KV, g, Dh, MB = 2, 2, 16, 16
    q = jnp.zeros((3, 8, KV, g, Dh), jnp.float32)
    leaf = jnp.zeros((9, 8, Dh), jnp.float32)
    table = jnp.zeros((2, MB), jnp.int32)
    meta = jnp.asarray([[0, 40, 8, 6], [0, 48, 2, 7], [1, 3, 0, 1]], jnp.int32)

    def stack(q, leaf_a, leaf_b):
        call = lambda leaf: SS.select_blocks(  # noqa: E731
            q, leaf, table, meta, block=8, stride=2, kernel=4, topk=4,
            window=8, init=1, dense_len=24, interpret=True)[0]
        return call(leaf_a) + call(leaf_b)

    jax.jit(stack).lower(q, leaf, leaf + 1)
    assert traced == [8]


def test_page_lists_hold_a_tiles_union_in_order():
    chosen = np.zeros((2, 2, 1, 6), bool)
    chosen[0, 0, 0, [0, 4]] = True
    chosen[0, 1, 0, [0, 2, 5]] = True
    plist, count, at = MS.page_lists(jnp.asarray(chosen), 128)
    assert count.tolist() == [[4], [0]]
    assert plist[0, 0, :4].tolist() == [0, 2, 4, 5]
    assert at.shape == (4, 1, 128)
    assert at[0, 0, :4].tolist() == [True, False, True, False]
    assert at[1, 0, :4].tolist() == [True, True, False, True]
    assert not np.any(np.asarray(at)[2:])
    assert MS.list_width(CFG, 8, 24) == 128
    assert MS.list_width(get_model_config("minicpm-sala"), 8, 1032) == 512
    assert MS.list_width(get_model_config("minicpm-sala"), 1, 1032) == 128


# -- the walk over a page list ----------------------------------------------------

H, KV, DH, BS, MB = 4, 2, 128, 16, 6


def _pool(seed=0, layers=2):
    rng = np.random.default_rng(seed)
    N = 3 * MB + 1
    shape = (layers, N, KV, BS, DH)
    k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(2))
    table = 1 + rng.permutation(N - 1)[:3 * MB].reshape(3, MB)
    return k, v, jnp.asarray(table, jnp.int32), rng


def _full_lists(G, counts):
    plist = np.zeros((G, KV, 128), np.int32)
    plist[:, :, :MB] = np.arange(MB)
    return jnp.asarray(plist), jnp.asarray(
        np.repeat(np.asarray(counts)[:, None], KV, 1), jnp.int32)


def test_the_walk_of_a_full_list_is_the_range_walk_bit_for_bit():
    """Decode rows and mixed tiles, the kernels writing the new rows in
    place: a list that names every page of the range gives the range walk's
    output and pool, to the bit. Bit for bit needs the two walks to fold the
    same pages a step (another P is another order of float32 sums): at these
    shapes the listed rule (up to 32 pages a step, ISSUE 52) and the range
    rule (up to 8) both stop at the table's width, asserted here."""
    from distributed_llm_inference_tpu.ops.paged_attention import _walk_shape

    for rows in (H // KV, 8 * (H // KV)):
        assert (_walk_shape(KV, BS, DH, 4, False, rows, MB, listed=True)[1]
                == _walk_shape(KV, BS, DH, 4, False, rows, MB)[1] == 4)
    k, v, table, rng = _pool()
    pos = jnp.asarray([37, 5, 80], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, H, DH)), jnp.float32)
    nk, nv = (jnp.asarray(rng.normal(size=(3, 1, KV, DH)), jnp.float32)
              for _ in range(2))
    active = jnp.asarray([True, True, False])
    write = (jnp.int32(1), nk, nv)
    want = paged_flash_attend(q, k, v, table, pos, None, active, write)
    plist, count = _full_lists(3, [37 // BS + 1, 1, 0])
    got = paged_flash_attend(q, k, v, table, pos, None, active, write,
                             (plist, count))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a mixed launch: a chunk of 12 from 30 (two tiles of 8), a decode row,
    # a dead tile
    tq = 8
    meta = jnp.asarray([[0, 30, 8, 0], [0, 38, 4, 0], [1, 70, 1, 1],
                        [1, 70, 0, 1]], jnp.int32)
    W = 4 * tq
    q = jnp.asarray(rng.normal(size=(W, H, DH)), jnp.float32)
    nk, nv = (jnp.asarray(rng.normal(size=(W, KV, DH)), jnp.float32)
              for _ in range(2))
    write = (jnp.int32(0), nk, nv)
    want = ragged_paged_attend(q, k, v, table, meta, None, write)
    plist, count = _full_lists(4, [38 // BS + 1, 42 // BS + 1, 70 // BS + 1, 0])
    chosen = jnp.asarray(np.arange(128)[None, None, :] < np.repeat(
        np.asarray(count), tq, 0)[:, :, None])
    got = ragged_paged_attend(q, k, v, table, meta, None, write,
                              (plist, count, chosen))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "case", sorted(LISTED_CASES) + sorted(LISTED_DECODE_CASES))
def test_the_walk_of_a_list_is_plain_attention_over_the_chosen_pages(case):
    """The listed walk at the shapes ISSUE 52 touches
    (tests/paged_walk_cases.py): 64-token pages, 32 a step; a list longer
    than a step whose count is not a multiple of P; a tile's own pages on
    either side of a compute block's edge; a tile of fewer than tq queries; a
    decode tile and decode rows; queries of one tile that chose disjoint
    pages; dead entries over memory that reads NaN."""
    if case in LISTED_DECODE_CASES:
        return check_listed_decode_case(case)
    check_listed_case(case)


def test_the_walk_of_a_selection_masks_per_query_what_the_tile_walks():
    """A tile of 8 queries at positions 88-95 whose queries chose different
    pages (each its own block and block 0; odd queries page 2, even ones
    page 3, KV head 1 never page 2): the kernel walks the union and each
    query attends its own choice, as plain attention under that mask."""
    k, v, table, rng = _pool(seed=3, layers=1)
    tq, start = 8, 88
    meta = jnp.asarray([[2, start, tq, 0]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(tq, H, DH)), jnp.float32)
    nk, nv = (jnp.asarray(rng.normal(size=(tq, KV, DH)), jnp.float32)
              for _ in range(2))
    chosen = np.zeros((1, tq, KV, MB), bool)
    chosen[0, :, :, [0, 5]] = True
    chosen[0, 1::2, 0, 2] = True
    chosen[0, 0::2, :, 3] = True
    plist, count, at = MS.page_lists(jnp.asarray(chosen), 128)
    assert count.tolist() == [[4, 3]]
    out, new_k, new_v = ragged_paged_attend(
        q, k, v, table, meta, None, (jnp.int32(0), nk, nv),
        (plist, count, at))
    # the row's keys and values with the tile's own rows put in
    rows = table[2]
    keys = np.asarray(new_k[0])[rows].transpose(1, 0, 2, 3).reshape(KV, -1, DH)
    vals = np.asarray(new_v[0])[rows].transpose(1, 0, 2, 3).reshape(KV, -1, DH)
    np.testing.assert_array_equal(keys[:, start:start + tq],
                                  np.asarray(nk).transpose(1, 0, 2))
    g = H // KV
    for t in range(tq):
        for h in range(H):
            kv = h // g
            reads = np.repeat(chosen[0, t, kv], BS) & (
                np.arange(MB * BS) <= start + t)
            s = keys[kv, reads] @ np.asarray(q[t, h]) * DH ** -0.5
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vals[kv, reads]
            np.testing.assert_allclose(np.asarray(out[t, h]), want,
                                       rtol=2e-4, atol=2e-5)
