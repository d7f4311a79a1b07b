"""Decayed linear attention (Lightning Attention's form) over a float32
matrix state a row and head.

Per head h with decay a_h = exp(-s_h) and a row's tokens t = 0, 1, ...:

    S_t = a_h S_{t-1} + k_t^T v_t        (S: [Dh, Dh], float32)
    o_t = q_t S_t

`linear_attend_rows` computes the same sums for a paged launch's FLAT
tokens chunk by chunk: the launch is one chunk. Within it a masked
`Q K^T` weighted by the decay's powers (a token sees its own row's earlier
tokens of the launch, which lie side by side on the flat axis); across
launches the carried state, read once a query tile (a tile's tokens are one
row's) and advanced by the launch's keys and values. `linear_attend_step`
is the recurrence itself, one token a row: the decode chunk's form. The
two agree to rounding (tests/test_sala_ops.py).

The products that read or write S run at `Precision.HIGHEST`: S is stated
float32, and a one-pass bfloat16 product would round it at every read. The
slopes s_h = 2^(-8 h / H), h = 1 .. H, are the Lightning Attention family's
fixed ones (assumed: cellbench/configs/minicpm-sala-9b-16l.json)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def decay_slopes(n_heads: int) -> jnp.ndarray:
    """s_h [H] float32: the decay of head h (1-based) is exp(-s_h)."""
    h = jnp.arange(1, n_heads + 1, dtype=F32)
    return jnp.exp2(-8.0 * h / n_heads)


@jax.named_scope("linear_scan")
def linear_attend_rows(q, k, v, state, tok_row, tq: int):
    """q, k, v [W, H, Dh]: a launch's flat tokens (q scaled); tok_row [W]
    int32 the fleet row of each (-1: launch padding, a dead row), a row's
    tokens contiguous and in order, every tile of tq tokens one row's;
    state [R, H, Dh, Dh] float32: what each row starts the launch from.
    Returns (o [W, H, Dh] float32, the rows' states after the launch: a
    row with no token keeps its own)."""
    W, H, Dh = q.shape
    R = state.shape[0]
    G = W // tq
    s = decay_slopes(H)
    live = tok_row >= 0
    rix = jnp.maximum(tok_row, 0)
    flat = jnp.arange(W, dtype=jnp.int32)
    first = jnp.full((R,), W, jnp.int32).at[rix].min(
        jnp.where(live, flat, W))
    count = jnp.zeros((R,), jnp.int32).at[rix].add(live.astype(jnp.int32))
    i = (flat - first[rix]).astype(F32)  # the token's index in its row
    n = count[rix].astype(F32)
    # within the launch: (q_w . k_u) a^(w - u) for u <= w of the same row
    dist = flat[:, None] - flat[None, :]
    same = (live[:, None] & live[None, :]
            & (tok_row[:, None] == tok_row[None, :]) & (dist >= 0))
    decay = jnp.where(
        same[None], jnp.exp(-s[:, None, None] * jnp.maximum(dist, 0)[None]),
        0.0)  # [H, W, W]
    a = jnp.einsum("whd,uhd->hwu", q, k, preferred_element_type=F32) * decay
    o = jnp.einsum("hwu,uhd->whd", a, v.astype(F32), precision=_HI)
    # across launches: q_w a^(i + 1) S_start, the state read once a tile
    tile_row = jnp.max(tok_row.reshape(G, tq), axis=1)
    at = jnp.maximum(tile_row, 0)
    qd = q.astype(F32) * jnp.exp(-s[None, :, None] * (i + 1.0)[:, None, None])
    o = o + jnp.einsum(
        "gthd,ghde->gthe", qd.reshape(G, tq, H, Dh), state[at], precision=_HI
    ).reshape(W, H, Dh)
    o = jnp.where(live[:, None, None], o, 0.0)
    # the state after: a^n S_start + sum_w a^(n - 1 - i_w) k_w^T v_w
    kd = jnp.where(
        live[:, None, None],
        k.astype(F32) * jnp.exp(
            -s[None, :, None] * (n - 1.0 - i)[:, None, None]), 0.0)
    part = jnp.einsum(
        "gthd,gthe->ghde", kd.reshape(G, tq, H, Dh),
        v.astype(F32).reshape(G, tq, H, Dh), precision=_HI)
    add = jnp.zeros_like(state).at[at].add(
        jnp.where((tile_row >= 0)[:, None, None, None], part, 0.0))
    keep = jnp.exp(-s[None, :] * count.astype(F32)[:, None])  # [R, H]
    return o, state * keep[:, :, None, None] + add


@jax.named_scope("linear_scan")
def linear_attend_step(q, k, v, state, active=None):
    """The recurrence, one token a row: q, k, v [R, H, Dh] (q scaled),
    state [R, H, Dh, Dh] float32, active [R] bool or None (a row that is
    not active keeps its state and reads zeros). Returns (o [R, H, Dh]
    float32, the new state)."""
    H = q.shape[1]
    a = jnp.exp(-decay_slopes(H))[None, :, None, None]
    new = a * state + k.astype(F32)[..., :, None] * v.astype(F32)[..., None, :]
    o = jnp.einsum("rhd,rhde->rhe", q.astype(F32), new, precision=_HI)
    if active is not None:
        on = active[:, None, None]
        new = jnp.where(on[..., None], new, state)
        o = jnp.where(on, o, 0.0)
    return o, new
