"""The gated delta rule with a decay a key CHANNEL (Kimi Delta Attention,
arXiv:2510.26692) over a float32 matrix state a row and head, over a paged
launch's FLAT tokens.

Per head (k_t, q_t [Dk] with |k_t| = 1, v_t [Dv], g_t [Dk] <= 0 the log of
the token's decay a channel, beta_t in (0, 2)) and a row's tokens t = 0, 1,
...:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                     (S: [Dk, Dv], float32)

`delta_rule_rows` computes the same launch by launch in chunks of CHUNK
places of the flat axis (the WY / UT form). A SEGMENT is what one row has in
one chunk (a row's tokens lie side by side, so a chunk holds whole segments
of one or several rows, and pairs of different rows are masked out). With G_i
the sum of g over the segment up to and with token i, u_i = beta_i (v_i -
k_i^T Diag(exp g_i) S_{i-1}) obeys

    (I + A) U = Diag(beta) (V - (K * exp G) S_0),
    A_ij = beta_i <k_i * exp(G_i - G_j), k_j> for j < i of the segment,

so with T = (I + A)^-1 Diag(beta), W = T (K * exp G) and U = T V (none of
which reads the state) a segment that starts from S is

    U' = U - W S
    o  = (Q * exp G) S + tril(<q_i * exp(G_i - G_j), k_j>) U'
    S <- Diag(exp G_end) S + (K * exp(G_end - G))^T U'

NO EXPONENT IS POSITIVE. exp(-G) alone overflows float32 inside one chunk at
the fastest decays (g down to -50 a token), so the pair weights exp(G_i -
G_j) are never factored over a chunk: inside a sub-chunk of SUB places they
are taken pair by pair and channel by channel, and between sub-chunks
against G at the END of the sub-chunk before the query's, r: exp(G_i - G_r)
and exp(G_r - G_j) with j <= r < i, both at most 1 (an exponent of a pair
that is not one segment's is clamped to 0 and the pair masked). A decay that
underflows to 0 is the recurrence's own. G starts anew with every segment,
so its differences lose nothing behind a long row.

(I + A)^-1 is taken by forward substitution inside the sub-chunks' 16 x 16
diagonal blocks (15 row steps for all blocks at once) and block by block
below them: the product form of the inverse would cancel where keys repeat.

Across chunks and launches the carried state goes through ONE Pallas program
(`_state_kernel`) over (head group, the rows that carry a token), as
ops/ssm_scan.py's does: a row's state comes into VMEM once, the row's chunks
are read against it and folded into it one after the other, and it goes back
to where it came from (the state leaf is the program's aliased output: a row
with no token costs no byte). impl "xla" is the same sums chunk by chunk in
XLA, over every row's state (the twin the CPU tests hold the program
against; no serving path).

A launch that gives every fleet row AT MOST ONE TOKEN by construction (the
decode program: flat place i is fleet row i's) is `delta_rule_step`: the
recurrence itself, of which every piece of the chunked form is the identity
(A = 0, T = beta, G = g), with the state stored transposed:

    Sd = S * exp(g)     r = sum_d Sd[:, d] k[d]     u = beta (v - r)
    S' = Sd + u k^T     o = sum_d S'[:, d] q[d]

in one Pallas program (`_step_kernel`) over (head group, the rows that carry
a token) with the same row list and aliased leaf, float32 on the vector
unit: a lane scaling, two lane reductions and an outer product a head, no
chunk, no cumulative sum, no solve and no matrix unit. Both forms read and
write the same leaf, so a row goes from one to the other at every hand-over
between a mixed launch and a decode chunk.

THE STATE'S LAYOUT. The leaf holds a head's state TRANSPOSED, [R, H, Dv, Dk]:
the key channels on the lanes, so that a channel's decay scales a lane and
the fold (U'^T K) writes whole rows.

The products that read or write S, and the solve, run at `Precision.HIGHEST`:
S is stated float32. tests/test_solar_ops.py holds both paths against the
recurrence itself in float64, token by token."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret
from .ssm_scan import _segmented_cumsum, row_spans

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# places of the flat axis a chunk holds, and a sub-chunk (module docstring)
CHUNK = 64
SUB = 16
# VMEM the program's blocks may take, second buffers included: inside the
# default scoped limit (16 MiB on v5e)
_STATE_VMEM_BYTES = 10 * 2**20
# the most heads a program of the one-token form holds (its body is written
# out a head: a head's v and o are lane slices of a transposed tile)
_STEP_HEADS = 16


def chunks_of(first: int, count: int) -> int:
    """Chunks a row's `count` tokens from flat place `first` are cut into
    (the host's count of what `_state_kernel` loops over)."""
    return 0 if count <= 0 else (first + count - 1) // CHUNK - first // CHUNK + 1


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _state_heads(H: int, Wp: int, Dk: int, Dv: int) -> int:
    """Heads a program holds: the most (a program's fixed cost is paid once
    a group and row) whose blocks fit `_STATE_VMEM_BYTES`: the group's six
    token operands and its output over the whole flat axis and a row's state
    in and out, two buffers each."""
    head = 4 * (Wp * (4 * _lanes(Dk) + 2 * _lanes(Dv) + _lanes(CHUNK))
                + 2 * Dv * _lanes(Dk))
    for Hg in range(H, 0, -1):
        if H % Hg == 0 and 2 * Hg * head <= _STATE_VMEM_BYTES:
            return Hg
    return 1


def _touched_first(touched):
    """(rows [R], n): the n rows that carry a token first, in order; every
    place past them names the last of them, whose blocks then neither move
    nor change."""
    R = touched.shape[0]
    n = jnp.sum(touched.astype(jnp.int32))
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    return order[jnp.minimum(jnp.arange(R), jnp.maximum(n - 1, 0))], n


def _state_kernel(rows_ref, n_ref, first_ref, count_ref, zero_ref, w_ref,
                  u_ref, qg_ref, kend_ref, dend_ref, aqk_ref, s_in_ref, o_ref,
                  s_out_ref, *, Hg: int):
    """One program per (head group g, place j): the j-th row that carries a
    token (rows_ref[j]; past the n_ref[0] rows that do, the last one's
    blocks stay where they are and nothing runs). Float32, heads first, the
    whole flat axis: w, qg, kend [Hg, Wp, Dk] (W, Q * exp G and K * exp(G_end
    - G) of the module docstring), u [Hg, Wp, Dv], aqk [Hg, Wp, CHUNK] (the
    pair weights of a token against its chunk's places), dend [Hg, Wp, Dk]
    (exp G_end of the token's segment). The row's tokens are first_ref[row]
    .. + count_ref[row]; a row with zero_ref[row] set starts from zeros,
    whatever its block holds. Writes the row's tokens' outputs into o [Hg,
    Wp, Dv] (every other token zeros) and the state after the launch."""
    j = pl.program_id(1)
    n = n_ref[0]
    row = rows_ref[j]
    first, count = first_ref[row], count_ref[row]
    zero = zero_ref[row] > 0
    nt = (((1,), (1,)), ((), ()))  # a @ b^T
    tn = (((0,), (0,)), ((), ()))  # a^T @ b

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((j == 0) & (n == 0))
    def _():  # no token at all: the block this program holds goes back
        s_out_ref[...] = s_in_ref[...]

    @pl.when(j < n)
    def _():
        c0 = first // CHUNK
        chunks = (first + count - 1) // CHUNK - c0 + 1

        def head(h, carry):
            S0 = s_in_ref[0, h]  # [Dv, Dk]
            S0 = jnp.where(zero, jnp.zeros_like(S0), S0)

            def chunk(b, S):
                start = pl.multiple_of((c0 + b) * CHUNK, CHUNK)
                at = pl.ds(start, CHUNK)
                tok = start + jax.lax.broadcasted_iota(
                    jnp.int32, (CHUNK, 1), 0)
                mine = (tok >= first) & (tok < first + count)
                w = jnp.where(mine, w_ref[h, at, :], 0.0)
                un = jnp.where(mine, u_ref[h, at, :] - jax.lax.dot_general(
                    w, S, nt, precision=_HI, preferred_element_type=F32), 0.0)
                o = jax.lax.dot_general(
                    qg_ref[h, at, :], S, nt, precision=_HI,
                    preferred_element_type=F32)
                o = o + jnp.dot(aqk_ref[h, at, :], un, precision=_HI,
                                preferred_element_type=F32)
                o_ref[h, at, :] = jnp.where(mine, o, o_ref[h, at, :])
                last = jnp.minimum(first + count, start + CHUNK) - 1
                kend = jnp.where(mine, kend_ref[h, at, :], 0.0)
                return S * dend_ref[h, pl.ds(last, 1), :] \
                    + jax.lax.dot_general(un, kend, tn, precision=_HI,
                                          preferred_element_type=F32)

            s_out_ref[0, h] = jax.lax.fori_loop(0, chunks, chunk, S0)
            return carry

        # (a loop, not Hg copies of the body: a step program traces and
        # lowers the kernel at every start, compile cache or not)
        jax.lax.fori_loop(0, Hg, head, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_state(w, u, qg, kend, dend, aqk, state, first, count, zero, *,
                interpret):
    """The pallas_call: the token operands of `_state_kernel` over all H
    heads, state [R, H, Dv, Dk] float32 (donate it: the output's buffer),
    first / count / zero [R] int32. Returns (o [H, Wp, Dv], the state
    after). Jitted, so that a stack's layers trace and lower ONE kernel a
    step program."""
    H, Wp, Dk = w.shape
    Dv = u.shape[2]
    R = state.shape[0]
    Hg = _state_heads(H, Wp, Dk, Dv)
    rows, n = _touched_first(count > 0)

    def tokens(width):
        return pl.BlockSpec((Hg, Wp, width), lambda g, j, *refs: (g, 0, 0))

    of_row = pl.BlockSpec(
        (1, Hg, Dv, Dk), lambda g, j, rows, *refs: (rows[j], g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(H // Hg, R),
        in_specs=[tokens(Dk), tokens(Dv), tokens(Dk), tokens(Dk), tokens(Dk),
                  tokens(CHUNK), of_row],
        out_specs=[tokens(Dv), of_row],
    )
    return pl.pallas_call(
        functools.partial(_state_kernel, Hg=Hg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(u.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operands: 5 prefetched scalars, six token operands, the state
        input_output_aliases={11: 1},
        interpret=interpret,
        name="delta_state",
    )(rows, jnp.reshape(n, (1,)), first, count, zero, w, u, qg, kend, dend,
      aqk, state)


def _delta_state_xla(w, u, qg, kend, dend, aqk, state, tok_row, zero):
    """`delta_state`'s sums in XLA, chunk after chunk over EVERY row's state
    (module docstring): tok_row [Wp] the padded axis' rows."""
    H, Wp, _ = w.shape
    R = state.shape[0]
    member = (tok_row[:, None] == jnp.arange(R)[None, :]).astype(F32)
    state = jnp.where(zero[:, None, None, None], 0.0, state)

    def chunk(S, xs):
        w, u, qg, kend, dend, aqk, m = xs  # [H, C, .], m [C, R]
        own = jnp.einsum("cr,rhvd->hcvd", m, S)  # a token's row's state
        un = (u - jnp.einsum("hcd,hcvd->hcv", w, own, precision=_HI)) \
            * jnp.max(m, axis=1)[None, :, None]
        o = jnp.einsum("hcd,hcvd->hcv", qg, own, precision=_HI) \
            + jnp.einsum("hcj,hjv->hcv", aqk, un, precision=_HI)
        # a row's decay over the chunk: its tokens all carry the segment's
        keep = jnp.einsum("cr,hcd->rhd", m, dend) / jnp.maximum(
            jnp.sum(m, axis=0), 1.0)[:, None, None]
        here = (jnp.sum(m, axis=0) > 0)[:, None, None, None]
        S = jnp.where(here, S * keep[:, :, None, :] + jnp.einsum(
            "cr,hcv,hcd->rhvd", m, un, kend, precision=_HI), S)
        return S, o

    def cut(a):  # [H, Wp, x] -> [chunks, H, CHUNK, x]
        return a.reshape(H, Wp // CHUNK, CHUNK, -1).transpose(1, 0, 2, 3)

    state, o = jax.lax.scan(
        chunk, state, tuple(map(cut, (w, u, qg, kend, dend, aqk)))
        + (member.reshape(Wp // CHUNK, CHUNK, R),))
    return o.transpose(1, 0, 2, 3).reshape(H, Wp, -1), state


def _unit_lower_inverse(A):
    """(I + A)^-1 for A [..., CHUNK, CHUNK] strictly lower triangular: by
    forward substitution in the SUB x SUB diagonal blocks, all of them at
    once, then block row by block row below them."""
    lead, n = A.shape[:-2], CHUNK // SUB
    eye = jnp.eye(SUB, dtype=F32)
    blocks = A.reshape(*lead, n, SUB, n, SUB)
    diag = jnp.stack([blocks[..., a, :, a, :] for a in range(n)], axis=-3)
    X = jnp.broadcast_to(eye, diag.shape)
    for i in range(1, SUB):  # row i from the rows above it
        row = eye[i] - jnp.einsum("...k,...kj->...j", diag[..., i, :], X,
                                  precision=_HI)
        X = X.at[..., i, :].set(row)
    inv = X[..., 0, :, :]
    for a in range(1, n):  # [[P, 0], [L, D]]^-1 = [[P', 0], [-D' L P', D']]
        low = A[..., a * SUB:(a + 1) * SUB, :a * SUB]
        low = -jnp.einsum("...ik,...kl,...lj->...ij", X[..., a, :, :], low,
                          inv, precision=_HI)
        zeros = jnp.zeros(lead + (a * SUB, SUB), F32)
        inv = jnp.concatenate([
            jnp.concatenate([inv, zeros], axis=-1),
            jnp.concatenate([low, X[..., a, :, :]], axis=-1)], axis=-2)
    return inv


def _pair_products(lhs, k, G):
    """sum_d lhs_i[d] k_j[d] exp(G_i[d] - G_j[d]) for the places i >= j of a
    chunk, no exponent above 0 (module docstring): lhs [n, H, C, CHUNK, Dk]
    (n left sides at once), k, G [H, C, CHUNK, Dk] -> [n, H, C, CHUNK,
    CHUNK], garbage above the diagonal and between segments."""
    n, H, C, _, Dk = lhs.shape
    S = CHUNK // SUB
    sub = (H, C, S, SUB, Dk)
    ls, ks, Gs = lhs.reshape((n,) + sub), k.reshape(sub), G.reshape(sub)
    # inside a sub-chunk: pair by pair
    decay = jnp.exp(jnp.minimum(
        Gs[:, :, :, :, None, :] - Gs[:, :, :, None, :, :], 0.0))
    near = jnp.sum(ls[:, :, :, :, :, None, :]
                   * (ks[:, :, :, None, :, :] * decay)[None], axis=-1)
    rows = []
    for a in range(S):
        parts = []
        if a:
            # against G at the end of the sub-chunk before: r
            Gr = Gs[:, :, a - 1, SUB - 1][:, :, None, :]  # [H, C, 1, Dk]
            left = ls[:, :, :, a] * jnp.exp(
                jnp.minimum(Gs[:, :, a] - Gr, 0.0))[None]
            right = k[:, :, :a * SUB] * jnp.exp(
                jnp.minimum(Gr - G[:, :, :a * SUB], 0.0))
            parts.append(jnp.einsum("nhcid,hcjd->nhcij", left, right,
                                    precision=_HI))
        parts.append(near[:, :, :, a])
        if a < S - 1:
            parts.append(jnp.zeros((n, H, C, SUB, (S - 1 - a) * SUB), F32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


@jax.named_scope("delta_scan")
def delta_rule_rows(q, k, v, g, beta, state, tok_row, tq: int, zero=None,
                    interpret=None, impl: str = "pallas"):
    """q, k [W, H, Dk] (q scaled, k of unit length), v [W, H, Dv]: a launch's
    flat tokens; g [W, H, Dk] float32 <= 0 the log decays; beta [W, H]
    float32; tok_row [W] int32 the fleet row of each (-1: launch padding, a
    dead row), a row's tokens contiguous and in order (`tq`, the launch's
    tile, is not read: a chunk is CHUNK places wherever a row starts); state
    [R, H, Dv, Dk] float32: what each row starts the launch from (donated:
    the states after come back in its buffer), but zeros for a row where
    zero [R] bool holds.
    Returns (o [W, H, Dv] float32, the rows' states after the launch: a row
    with no token keeps its own, untouched)."""
    del tq
    W, H, Dk = q.shape
    R = state.shape[0]
    Wp = -(-W // CHUNK) * CHUNK
    C = Wp // CHUNK
    tok_row = jnp.pad(tok_row, (0, Wp - W), constant_values=-1)
    live, rix, first, count, starts = row_spans(tok_row, R)
    flat = jnp.arange(Wp, dtype=jnp.int32)

    def heads_first(a):  # [W, H, x] -> [H, C, CHUNK, x], dead tokens zeros
        a = jnp.pad(a.astype(F32), ((0, Wp - W), (0, 0), (0, 0)))
        a = jnp.where(live[:, None, None], a, 0.0)
        return a.transpose(1, 0, 2).reshape(H, C, CHUNK, a.shape[2])

    g = jnp.where(live[:, None, None],
                  jnp.pad(g.astype(F32), ((0, Wp - W), (0, 0), (0, 0))), 0.0)
    # G: the running sum of g over the token's segment, and at its end
    G = _segmented_cumsum(g.reshape(Wp, H * Dk),
                          starts | (flat % CHUNK == 0))
    last = jnp.minimum(first[rix] + count[rix] - 1, flat | (CHUNK - 1))
    Gend = G[jnp.where(live, last, flat)]

    def cut(a):  # [Wp, H Dk] -> [H, C, CHUNK, Dk]
        return a.reshape(Wp, H, Dk).transpose(1, 0, 2).reshape(
            H, C, CHUNK, Dk)

    G, Gend = cut(G), cut(Gend)
    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    beta = heads_first(beta[:, :, None])  # [H, C, CHUNK, 1]
    rows = tok_row.reshape(C, CHUNK)
    same = (rows[:, :, None] == rows[:, None, :]) & (rows >= 0)[:, :, None]
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    kk, qk = _pair_products(jnp.stack([k, q]), k, G)
    A = jnp.where(same & jnp.tril(lower, -1), kk * beta, 0.0)
    aqk = jnp.where(same & lower, qk, 0.0)
    T = _unit_lower_inverse(A) * beta[:, :, :, 0][:, :, None, :]
    w = jnp.einsum("hcij,hcjd->hcid", T, k * jnp.exp(G), precision=_HI)
    u = jnp.einsum("hcij,hcjd->hcid", T, v, precision=_HI)

    def flat_axis(a):
        return a.reshape(H, Wp, a.shape[-1])

    operands = tuple(map(flat_axis, (
        w, u, q * jnp.exp(G), k * jnp.exp(Gend - G), jnp.exp(Gend), aqk)))
    zero = jnp.zeros((R,), bool) if zero is None else zero & (count > 0)
    if impl == "xla":
        o, state = _delta_state_xla(*operands, state, tok_row, zero)
    else:
        o, state = delta_state(
            *operands, state, first, count, zero.astype(jnp.int32),
            interpret=resolve_interpret(interpret))
    o = o[:, :W].transpose(1, 0, 2)
    return jnp.where(live[:W, None, None], o, 0.0), state


def _step_heads(H: int, Dk: int, Dv: int) -> int:
    """Heads a program of the one-token form holds: the most (up to
    `_STEP_HEADS`) that tile a block's second-last axis and whose state
    blocks in and out, two buffers each, fit `_STATE_VMEM_BYTES`."""
    for Hg in range(min(H, _STEP_HEADS), 0, -1):
        if H % Hg == 0 and (Hg % 8 == 0 or Hg == H) \
                and 4 * Hg * Dv * _lanes(Dk) * 4 <= _STATE_VMEM_BYTES:
            return Hg
    return H


def _step_kernel(rows_ref, n_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                 s_in_ref, o_ref, s_out_ref, v_rows, o_cols, *, Hg: int):
    """One program per (head group, place j): the j-th row that carries a
    token (`_state_kernel`'s row list). q, k, g [1, Hg, Dk], v [1, Hg, Dv],
    beta [1, Hg, 1] float32: the row's one token; the row's state [1, Hg, Dv,
    Dk] in and out; o [1, Hg, Dv]. The recurrence of the module docstring a
    head, on the vector unit: r, u and o come out of their lane reductions
    as COLUMNS [Dv, 1], so v goes in and o comes out through one transposed
    tile a program (v_rows [T, Dv], o_cols [Dv, T])."""
    j = pl.program_id(1)
    n = n_ref[0]

    @pl.when((j == 0) & (n == 0))
    def _():  # no token at all: the block this program holds goes back
        s_out_ref[...] = s_in_ref[...]

    @pl.when(j < n)
    def _():
        v_rows[0:Hg, :] = v_ref[0]
        v_cols = v_rows[...].T  # [Dv, T]: head h's v down column h
        for h in range(Hg):
            at = slice(h, h + 1)
            k = k_ref[0, at, :]  # [1, Dk]
            Sd = s_in_ref[0, h] * jnp.exp(g_ref[0, at, :])  # [Dv, Dk]
            u = beta_ref[0, at, :] * (
                v_cols[:, at] - jnp.sum(Sd * k, axis=1, keepdims=True))
            S = Sd + u * k
            s_out_ref[0, h] = S
            o_cols[:, at] = jnp.sum(S * q_ref[0, at, :], axis=1,
                                    keepdims=True)
        o_ref[0] = o_cols[...].T[0:Hg, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_step(q, k, v, g, beta, state, live, *, interpret):
    """The pallas_call of the one-token form: q, k, g [R, H, Dk], v [R, H,
    Dv], beta [R, H, 1] float32 (place i is fleet row i's), state [R, H, Dv,
    Dk] float32 (donate it: the output's buffer), live [R] bool the rows
    that carry a token. Returns (o [R, H, Dv], a dead row's undefined; the
    state after). Jitted, so that a stack's layers trace and lower ONE
    kernel a step program."""
    R, H, Dk = q.shape
    Dv = v.shape[2]
    Hg = _step_heads(H, Dk, Dv)
    rows, n = _touched_first(live)

    def of_row(*tail):
        return pl.BlockSpec(
            (1, Hg) + tail,
            lambda g, j, rows, n: (rows[j], g) + (0,) * len(tail))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // Hg, R),
        in_specs=[of_row(Dk), of_row(Dk), of_row(Dv), of_row(Dk), of_row(1),
                  of_row(Dv, Dk)],
        out_specs=[of_row(Dv), of_row(Dv, Dk)],
        scratch_shapes=[pltpu.VMEM((_lanes(Hg), Dv), F32),
                        pltpu.VMEM((Dv, _lanes(Hg)), F32)],
    )
    return pl.pallas_call(
        functools.partial(_step_kernel, Hg=Hg),
        grid_spec=grid_spec,
        # (the leaf is STATED in HBM, and its aliased operand with it: left
        # to choose, the compiler's memory-space assignment carries a whole
        # 64 MiB leaf to the chip's other memory and back around every call
        # in the decode loop, for the few MB a step's live rows move)
        out_shape=[jax.ShapeDtypeStruct(v.shape, F32),
                   pltpu.HBM(state.shape, F32)],
        # operands: 2 prefetched scalars, the token's five, the state
        input_output_aliases={7: 1},
        interpret=interpret,
        name="delta_step",
    )(rows, jnp.reshape(n, (1,)), q, k, v, g, beta, state)


def _delta_step_xla(q, k, v, g, beta, state, live):
    """`delta_step`'s sums in XLA over EVERY row's state."""
    Sd = state * jnp.exp(g)[:, :, None, :]
    u = beta * (v - jnp.sum(Sd * k[:, :, None, :], axis=-1))
    S = Sd + u[:, :, :, None] * k[:, :, None, :]
    o = jnp.sum(S * q[:, :, None, :], axis=-1)
    return o, jnp.where(live[:, None, None, None], S, state)


@jax.named_scope("delta_scan")
def delta_rule_step(q, k, v, g, beta, state, tok_row, interpret=None,
                    impl: str = "pallas"):
    """`delta_rule_rows` for a launch whose flat place i is fleet row i's
    ONE token (tok_row [R] int32: i, or -1 for a row that carries none): q,
    k, g [R, H, Dk], v [R, H, Dv], beta [R, H]; state [R, H, Dv, Dk] float32
    (donated). The recurrence itself (module docstring); no row starts from
    zeros here (the decode program starts no tenant).
    Returns (o [R, H, Dv] float32, zeros for a row with no token; the rows'
    states after: a row with no token keeps its own, untouched)."""
    live = tok_row >= 0
    operands = tuple(a.astype(F32) for a in (q, k, v, g, beta[:, :, None]))
    if impl == "xla":
        o, state = _delta_step_xla(*operands, state, live)
    else:
        o, state = delta_step(*operands, state, live,
                              interpret=resolve_interpret(interpret))
    return jnp.where(live[:, None, None], o, 0.0), state
