"""The operators ISSUE 48 adds, each against a plain form of itself at tiny
sizes on the CPU: the chunked decayed linear-attention scan against the
token-by-token recurrence; the block selection against the reference's
(cellbench/reference/sparse_linear_hybrid.py), tie rule included; the paged
walk over a page LIST against the range walk (a full list: bit for bit) and
against the gather path under a selection a query.
"""

import os
import sys

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
    return MS.linear_attention(CFG, lp, h.astype(CFG.jnp_dtype), pool, 0,
                               rows, pos, cos, sin, 8)


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


@pytest.mark.parametrize("tie", [False, True], ids=["random", "ties"])
def test_the_selection_is_the_references(tie):
    """Every query of a 96-token row (12 blocks of 8; the first 23 below
    the tiny dense length): the blocks `select_blocks` picks from the
    pool's compressed keys are the reference's. `ties`: every key the same,
    so every block scores alike and the lower block wins, in both."""
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
    got = np.asarray(MS.select_blocks(
        cfg, q.reshape(T // tq, tq, KV, g, Dh), ck, t.reshape(T // tq, tq)))
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
    output and pool, to the bit."""
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
