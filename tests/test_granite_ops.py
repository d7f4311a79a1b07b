"""The operators ISSUE 51 adds, each against a plain form of itself at tiny
sizes on the CPU: the launch form of the state-space scan (the within-launch
part in XLA, the carried state's interpreted kernel) against the recurrence
itself, token by token, for one row and for several rows in one flat launch,
with dt spanning 0.001-10; the causal convolution over a launch's flat
tokens against the shifted sums of the reference
(cellbench/reference/ssm_hybrid.py), across a launch boundary; the state
leaf's packed layout; attention without rotary at 1/64 against the
reference's.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.ops import ssm_scan as S

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "cellbench"))

from harness import manifest  # noqa: E402

REF = manifest.load_module("reference", "ssm_hybrid")


def _recurrence(x, dt, A, B, C, S0):
    """One row's tokens x [n, H, P], dt [n, H], B, C [n, N] from the state
    S0 [H, P, N], one at a time in float64."""
    x, dt, A, B, C, St = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, S0))
    out = []
    for t in range(x.shape[0]):
        St = np.exp(dt[t] * A)[:, None, None] * St + (
            (dt[t][:, None] * x[t])[:, :, None] * B[t][None, None, :])
        out.append(np.einsum("hpn,n->hp", St, C[t]))
    return np.stack(out), St


# a launch's flat axis: (tile, [(fleet row or -1 for a tile of launch padding,
# its tokens)]), 4 fleet rows, row 1 never carrying a token
LAUNCHES = {
    "one-row": (8, [(0, 21)]),
    # a long chunk, a decode token, a dead tile between, a row with no token
    "tq1": (1, [(0, 12), (-1, 0), (2, 1), (3, 8)]),
    "tq4": (4, [(0, 12), (-1, 0), (2, 4), (3, 8)]),
    # a row whose tokens span 25 tiles (two blocks of the program's 128, the
    # second ending with the flat axis) beside rows of one token
    "tiles-of-8": (8, [(2, 1), (0, 200), (-1, 0), (3, 1)]),
    # a decode step: one token a row, a tile each
    "decode": (1, [(0, 1), (1, 1), (2, 1), (3, 1)]),
    # a mixed launch's live tokens packed side by side (ISSUE 54: no tiles, so
    # tq 1): decode rows' single tokens, then a chunk that starts at flat
    # token 2 and whose second block of 128 ends with the axis, padding last
    "compact": (1, [(2, 1), (3, 1), (0, 203)] + [(-1, 0)] * 11),
}


def _flat(tile, spans):
    tok_row = []
    for row, n in spans:
        tiles = max(1, -(-n // tile))
        tok_row += [row] * n + [-1] * (tiles * tile - n)
    return np.asarray(tok_row, np.int32)


@pytest.mark.parametrize("name", list(LAUNCHES))
@pytest.mark.parametrize("H,P,N", [(8, 64, 16), (16, 16, 8)])
def test_the_launch_form_of_the_scan_is_the_recurrence(name, H, P, N):
    """y and the state after, a row at a time, against the recurrence from
    the row's own start state; dt log-uniform on 0.001-10 with a in 1-16, so
    a row's decay runs from 0.999 a token to exp(-160): nothing overflows,
    nothing turns to NaN, and a state that decays to nothing is the
    recurrence's own zero. Rows with no token keep their state bit for bit;
    dead tokens read zeros."""
    tile, spans = LAUNCHES[name]
    tok_row = _flat(tile, spans)
    W, R = len(tok_row), 4
    rng = np.random.default_rng(len(name) + H)
    x = rng.normal(size=(W, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(10.0), size=(W, H))).astype(np.float32)
    A = -rng.uniform(1.0, 16.0, size=(H,)).astype(np.float32)
    B = rng.normal(size=(W, N)).astype(np.float32)
    C = rng.normal(size=(W, N)).astype(np.float32)
    S0 = rng.normal(size=(R, H, P, N)).astype(np.float32)
    y, after = S.ssm_scan_rows(
        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A), jnp.asarray(B), jnp.asarray(C),
        S.pack_state(jnp.asarray(S0)), jnp.asarray(tok_row), tile)
    assert after.shape == (R,) + S.state_shape(H, P, N)
    y, after = np.asarray(y), np.asarray(S.unpack_state(after, P))
    assert np.isfinite(y).all() and np.isfinite(after).all()
    for r in range(R):
        at = np.flatnonzero(tok_row == r)
        if not len(at):
            assert (after[r] == S0[r]).all()
            continue
        want_y, want_S = _recurrence(x[at], dt[at], A, B[at], C[at], S0[r])
        np.testing.assert_allclose(y[at], want_y, rtol=2e-4, atol=2e-4 * np.abs(want_y).max())
        np.testing.assert_allclose(after[r], want_S, rtol=2e-4,
                                   atol=2e-4 * np.abs(want_S).max())
    assert (y[tok_row < 0] == 0).all()


def test_a_row_flagged_zero_starts_from_zeros_and_its_neighbours_carry_on():
    """`zero` [R]: a re-let slot's row reads its block as zeros, whatever the
    previous tenant left in it; the rows beside it start from their own."""
    tile, spans = LAUNCHES["tq4"]
    tok_row = _flat(tile, spans)
    W, R, H, P, N = len(tok_row), 4, 8, 64, 16
    rng = np.random.default_rng(5)
    x = rng.normal(size=(W, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, size=(W, H)).astype(np.float32)
    A = -rng.uniform(1.0, 4.0, size=(H,)).astype(np.float32)
    B, C = (rng.normal(size=(W, N)).astype(np.float32) for _ in range(2))
    S0 = rng.normal(size=(R, H, P, N)).astype(np.float32)
    zero = np.array([True, False, False, True])
    y, after = S.ssm_scan_rows(
        *(jnp.asarray(a) for a in (x, dt, A, B, C)), S.pack_state(jnp.asarray(S0)),
        jnp.asarray(tok_row), tile, zero=jnp.asarray(zero))
    after = np.asarray(S.unpack_state(after, P))
    for r in (0, 2, 3):
        at = np.flatnonzero(tok_row == r)
        want_y, want_S = _recurrence(x[at], dt[at], A, B[at], C[at],
                                     S0[r] * (not zero[r]))
        np.testing.assert_allclose(np.asarray(y)[at], want_y, rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(after[r], want_S, rtol=2e-4, atol=1e-4)
    assert (after[1] == S0[1]).all()


@pytest.mark.parametrize("H,P,N,shape", [(64, 64, 128, (32, 128, 128)),
                                          (16, 16, 8, (2, 8, 128)),
                                          (3, 64, 16, (3, 16, 64))])
def test_the_state_leaf_packs_heads_on_whole_lanes_and_back(H, P, N, shape):
    assert S.state_shape(H, P, N) == shape
    full = np.random.default_rng(0).normal(size=(2, H, P, N)).astype(np.float32)
    packed = S.pack_state(jnp.asarray(full))
    assert packed.shape == (2,) + shape
    pack = shape[-1] // P
    # head h's [P, N] lies transposed on lanes (h % pack) P .. of row h // pack
    h = H - 1
    np.testing.assert_array_equal(
        np.asarray(packed)[1, h // pack, :, (h % pack) * P:(h % pack + 1) * P], full[1, h].T)
    np.testing.assert_array_equal(np.asarray(S.unpack_state(packed, P)), full)


def test_the_convolution_over_flat_tokens_is_the_shifted_sums_across_a_launch_boundary():
    """Two rows' sequences convolved whole by the reference's K shifted sums,
    and launch by launch over flat tokens with the state carried between:
    a row's first launch from zeros, its second from the K - 1 inputs the
    first left, a decode token at the end; the other row rides along at
    other places of the flat axis, and a row with no token keeps its state."""
    K, Cw, R, tile = 4, 24, 3, 4
    rng = np.random.default_rng(2)
    seqs = {0: rng.normal(size=(15, Cw)).astype(np.float32),
            2: rng.normal(size=(7, Cw)).astype(np.float32)}
    w = rng.normal(size=(K, Cw)).astype(np.float32)
    b = rng.normal(size=(Cw,)).astype(np.float32)
    want = {r: np.asarray(REF.conv(jnp.asarray(s), jnp.asarray(w), jnp.asarray(b)))
            for r, s in seqs.items()}
    launches = [[(0, 0, 9), (2, 0, 2)], [(2, 2, 4), (0, 9, 5)], [(0, 14, 1), (2, 6, 1)]]
    state = jnp.asarray(rng.normal(size=(R, K - 1, Cw)).astype(np.float32))
    state = state.at[jnp.asarray([0, 2])].set(0.0)  # (the two rows start cold)
    keep = np.asarray(state[1])
    got = {r: [] for r in seqs}
    for spans in launches:
        tok_row = _flat(tile, [(r, n) for r, _, n in spans])
        x = np.zeros((len(tok_row), Cw), np.float32)
        for r, start, n in spans:
            at = np.flatnonzero(tok_row == r)
            x[at] = seqs[r][start:start + n]
        out, state = S.causal_conv_rows(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                                        state, jnp.asarray(tok_row))
        for r, _, _ in spans:
            got[r].append(np.asarray(out)[tok_row == r])
    for r in seqs:
        np.testing.assert_allclose(np.concatenate(got[r]), want[r], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(state[r]), seqs[r][-(K - 1):])
    np.testing.assert_array_equal(np.asarray(state[1]), keep)


def test_attention_without_rotary_at_a_direct_multiplier_is_the_references():
    """The family's attention: no position signal and scores x 1/64 (not
    64^-1/2): the program's `attend` at cfg.query_scale against the
    reference's masked softmax; shifting every position changes nothing."""
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from distributed_llm_inference_tpu.ops.attention import attend, causal_mask

    for name in ("test-granite-tiny", "granite-4.0-h-micro"):
        cfg = get_model_config(name)
        assert cfg.query_scale == 1 / 64 != cfg.head_dim ** -0.5 and cfg.kv_pack == 2
    T, D, H, KV, Dh = 16, 32, 4, 2, 64
    rng = np.random.default_rng(4)
    u = rng.normal(size=(T, D)).astype(np.float32)
    lp = {n: jnp.asarray(rng.normal(size=s).astype(np.float32) * D ** -0.5) for n, s in (
        ("wq", (D, H * Dh)), ("wk", (D, KV * Dh)), ("wv", (D, KV * Dh)),
        ("wo", (H * Dh, D)))}
    REF.Q_BLOCK, block = T, REF.Q_BLOCK
    try:
        with jax.default_matmul_precision("highest"):
            want = np.asarray(REF.attention_op(jnp.asarray(u), lp, H=H, KV=KV, Dh=Dh,
                                               att=1 / 64))
    finally:
        REF.Q_BLOCK = block
    q = (u @ np.asarray(lp["wq"])).reshape(1, T, H, Dh)
    k = (u @ np.asarray(lp["wk"])).reshape(1, T, KV, Dh).transpose(0, 2, 1, 3)
    v = (u @ np.asarray(lp["wv"])).reshape(1, T, KV, Dh).transpose(0, 2, 1, 3)
    o = attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal_mask(0, T, T),
               scale=cfg.query_scale)
    got = np.asarray(o).reshape(T, H * Dh) @ np.asarray(lp["wo"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
