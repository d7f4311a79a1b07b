"""The Mamba-2 state-space scan (SSD) over a float32 matrix state a row and
head, and the causal depthwise convolution in front of it, both over a
paged launch's FLAT tokens.

Per head h (A_h < 0 a scalar, dt_t > 0 the token's own step, B_t and C_t
[N] shared by all heads: one group) and a row's tokens t = 0, 1, ...:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T     (S: [P, N], float32)
    y_t = S_t C_t + D_h x_t

`ssm_scan_rows` computes the same sums launch by launch: the launch is the
chunk. With cum_w the sum of dt A over the row's tokens of the launch up to
and with w (<= 0, falling), a token reads

    y_w = exp(cum_w) S_start C_w
          + sum_{u <= w, same row} (C_w . B_u) exp(cum_w - cum_u) dt_u x_u

and the row leaves S = exp(cum_last) S_start + sum_u exp(cum_last - cum_u)
dt_u x_u B_u^T. Every exponent is a sum of dt A over some tokens, so <= 0:
nothing overflows whatever dt, and a decay that underflows to 0 is the
recurrence's own. Where linear attention's decay is a constant's power
(ops/linear_attention.py), this one is a cumulative product of per-token
gates, so cum is a segmented cumulative sum over the flat axis (it starts
anew at every row's first token: differences of one running sum over the
whole launch would lose the small steps behind a large one).

Within the launch: the masked `C B^T` weighted by exp of cum's differences,
in XLA (a token sees its own row's earlier tokens of the launch, which lie
side by side on the flat axis). Across launches the carried state, in one
Pallas program (`_scan_kernel`) over (head group, the rows that carry a
token): a row's state comes into VMEM once, the row's tokens are read
against it and folded into it, and it goes back to where it came from (the
state leaf is the program's aliased output: a row with no token costs no
byte). B and C are one block for all heads; the per-head decays ride x
(pre-scaled by exp(cum_last - cum_u) dt_u) and the read's result
(post-scaled by exp(cum_w)), never an operand a head. A decode step (one
token a row) is the same call.

THE STATE'S LAYOUT. A head's state is [P, N] = 64 x 128 numbers; the leaf
holds it transposed and `pack` heads side by side, [R, H / pack, N, pack x
P] (`state_shape`): whole 128-lane rows at head dim 64, the same bytes, so
that a token's read is sum_n C[n] S[n, :] (a product with a column over
whole vector registers, both heads of a pair at once) and its write
b_col x_row, with B and C turned into columns once a row for all heads.
`pack_state` / `unpack_state` go between this and [R, H, P, N].

The products that read or write S run at `Precision.HIGHEST`: S is stated
float32, and a one-pass bfloat16 product would round it at every read.
tests/test_granite_ops.py holds both paths against the recurrence itself,
token by token."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

# tokens a product of the scan's program takes at once: the array's height
_SCAN_TOKENS = 128
# VMEM the program's blocks may take, second buffers included: inside the
# default scoped limit (16 MiB on v5e)
_SCAN_VMEM_BYTES = 10 * 2**20


def head_pack(H: int, P: int) -> int:
    """Heads the state leaf holds side by side on a 128-lane row."""
    pack = 128 // P if 128 % P == 0 else 1
    return pack if H % pack == 0 else 1


def state_shape(H: int, P: int, N: int) -> tuple:
    """A row's state in the leaf: [H / pack, N, pack x P] (module doc)."""
    pack = head_pack(H, P)
    return (H // pack, N, pack * P)


def pack_state(S):
    """[..., H, P, N] -> the leaf's layout [..., H / pack, N, pack x P]."""
    *lead, H, P, N = S.shape
    pack = head_pack(H, P)
    S = S.reshape(*lead, H // pack, pack, P, N)
    return jnp.moveaxis(S, -1, -3).reshape(*lead, H // pack, N, pack * P)


def unpack_state(S, P: int):
    """The leaf's layout -> [..., H, P, N]."""
    *lead, G, N, L = S.shape
    pack = L // P
    S = jnp.moveaxis(S.reshape(*lead, G, N, pack, P), -3, -1)
    return S.reshape(*lead, G * pack, P, N)


def _scan_groups(G: int, Wp: int, N: int, L: int) -> int:
    """Head groups (rows of the packed state) a program of the scan holds:
    the most (a program's fixed cost is paid once a group and row) whose
    blocks fit `_SCAN_VMEM_BYTES`: B and C once, the group's x and output
    tokens and a row's state in and out, two buffers each."""
    for Gg in range(G, 0, -1):
        if G % Gg:
            continue
        if 2 * 4 * (2 * Wp * N + Gg * (2 * Wp * L + 2 * N * L)) \
                <= _SCAN_VMEM_BYTES:
            return Gg
    return 1


def _scan_kernel(rows_ref, n_ref, first_ref, count_ref, zero_ref, c_ref,
                 b_ref, x_ref, keep_ref, s_in_ref, o_ref, s_out_ref, cols, *,
                 Gg: int, Tb: int, Wp: int, align: int):
    """One program per (head group g, place j): the j-th row that carries a
    token (rows_ref[j]; past the n_ref[0] rows that do, the last one's
    blocks stay where they are and nothing runs). c / b [Wp, N] float32:
    the launch's C and B, all heads'; x [Gg, Wp, L] float32: the tokens' x
    under exp(cum_last - cum) dt, `pack` heads side by side, zeros where a
    token is dead; the row's tokens are first_ref[row] .. + count_ref[row].
    keep [1, Gg, 1, L]: exp(cum_last), a head's on its lanes. A row with
    zero_ref[row] set starts from zeros, whatever its block holds (a slot
    let again: no pass over the leaf to reset it). Writes the row's tokens'
    read of the state it starts from, C S, into o [Gg, Wp, L] (every other
    token zeros) and the state after the launch."""
    j = pl.program_id(1)
    n = n_ref[0]
    row = rows_ref[j]
    first, count = first_ref[row], count_ref[row]
    zero = zero_ref[row] > 0

    def state0(g):
        S = s_in_ref[0, g]  # [N, L]
        return jnp.where(zero, jnp.zeros_like(S), S)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((j == 0) & (n == 0))
    def _():  # no token at all: the block this program holds goes back
        s_out_ref[...] = s_in_ref[...]

    @pl.when((j < n) & (count == 1))
    def _():
        # one token: the recurrence on the vector unit (a [1, N] x [N, L]
        # product would load the array's weights a head for one row). C and
        # B as columns, once for every head: two rows transposed together.
        at = pl.ds(first, 1)
        cols[0:1, :] = c_ref[at, :]
        cols[1:2, :] = b_ref[at, :]
        t = cols[...].T  # [N, .]
        c_col, b_col = t[:, 0:1], t[:, 1:2]

        def group(g, carry):
            S = state0(g)
            o_ref[g, at, :] = jnp.sum(c_col * S, axis=0, keepdims=True)
            s_out_ref[0, g] = keep_ref[0, g] * S + b_col * x_ref[g, at, :]
            return carry

        jax.lax.fori_loop(0, Gg, group, 0)

    @pl.when((j < n) & (count > 1))
    def _():
        def block(b, carry):
            lo = first + b * Tb
            # (the last block of the flat axis ends with it: the tokens it
            # shares with the block before are masked out)
            start = jnp.minimum(lo, Wp - Tb) if Wp > Tb else 0
            if align > 1 and Wp > Tb:
                start = pl.multiple_of(start, align)
            at = pl.ds(start, Tb)
            tok = start + jax.lax.broadcasted_iota(jnp.int32, (Tb, 1), 0)
            mine = (tok >= lo) & (tok < first + count)
            cb = c_ref[at, :]
            bb = jnp.where(mine, b_ref[at, :], 0.0)

            def group(g, carry):
                S = state0(g)
                read = jnp.dot(cb, S, precision=_HI,
                               preferred_element_type=F32)
                o_ref[g, at, :] = jnp.where(mine, read, o_ref[g, at, :])
                # (the first block starts the state after: keep x S)
                was = jnp.where(b == 0, keep_ref[0, g] * S, s_out_ref[0, g])
                s_out_ref[0, g] = was + jax.lax.dot_general(
                    bb, x_ref[g, at, :], (((0,), (0,)), ((), ())),
                    precision=_HI, preferred_element_type=F32)
                return carry

            # (a loop, not Gg copies of the body: a step program traces and
            # lowers the kernel at every start, compile cache or not)
            return jax.lax.fori_loop(0, Gg, group, carry)

        jax.lax.fori_loop(0, (count + Tb - 1) // Tb, block, 0)


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def ssm_scan(c, b, x, keep, state, first, count, zero, *, tq: int,
             interpret):
    """The pallas_call: c, b [Wp, N] float32, x [G, Wp, L] float32, keep
    [R, G, L] float32, state [R, G, N, L] float32 (donate it: the output's
    buffer), first / count / zero [R] int32. Returns (o [G, Wp, L], the
    state after). Jitted, so that a stack's layers trace and lower ONE kernel a
    step program."""
    G, Wp, L = x.shape
    R, N = state.shape[0], state.shape[2]
    Gg = _scan_groups(G, Wp, N, L)
    Tb = min(_SCAN_TOKENS, Wp)
    # the rows that carry a token first, in order; every place past them
    # names the last of them, whose blocks then neither move nor change
    touched = count > 0
    n = jnp.sum(touched.astype(jnp.int32))
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(R), jnp.maximum(n - 1, 0))]

    shared = pl.BlockSpec((Wp, N), lambda g, j, *refs: (0, 0))
    tokens = pl.BlockSpec((Gg, Wp, L), lambda g, j, *refs: (g, 0, 0))

    def of_row(*tail):
        return pl.BlockSpec(
            (1, Gg) + tail,
            lambda g, j, rows, *refs: (rows[j], g) + (0,) * len(tail))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(G // Gg, R),
        in_specs=[shared, shared, tokens, of_row(1, L), of_row(N, L)],
        out_specs=[tokens, of_row(N, L)],
        scratch_shapes=[pltpu.VMEM((max(128, N), N), F32)],
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, Gg=Gg, Tb=Tb, Wp=Wp,
                          align=8 if tq % 8 == 0 else 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operands: 5 prefetched scalars, c, b, x, keep, the state
        input_output_aliases={9: 1},
        interpret=interpret,
        name="ssm_scan",
    )(rows, jnp.reshape(n, (1,)), first, count, zero, c, b, x,
      keep[:, :, None, :], state)


def row_spans(tok_row, R: int):
    """(live [W] bool, rix [W], first [R], count [R], starts [W] bool) of a
    launch's flat tokens: where each fleet row's tokens begin and how many
    they are (a row's tokens lie side by side, in order)."""
    W = tok_row.shape[0]
    live = tok_row >= 0
    rix = jnp.maximum(tok_row, 0)
    flat = jnp.arange(W, dtype=jnp.int32)
    first = jnp.full((R,), W, jnp.int32).at[rix].min(jnp.where(live, flat, W))
    count = jnp.zeros((R,), jnp.int32).at[rix].add(live.astype(jnp.int32))
    return live, rix, first, count, live & (flat == first[rix])


def _segmented_cumsum(a, starts):
    """Cumulative sums of a [W, H] along the flat axis that begin anew at
    every token where starts [W] holds."""
    def combine(left, right):
        (fl, vl), (fr, vr) = left, right
        return fl | fr, jnp.where(fr, vr, vl + vr)

    flags = jnp.broadcast_to(starts[:, None], a.shape)
    return jax.lax.associative_scan(combine, (flags, a))[1]


@jax.named_scope("ssm_scan")
def ssm_scan_rows(x, dt, A, B, C, state, tok_row, tq: int, zero=None,
                  interpret=None):
    """x [W, H, P]: a launch's flat tokens; dt [W, H] float32 > 0; A [H]
    float32 < 0; B, C [W, N]; tok_row [W] int32 the fleet row of each (-1:
    launch padding, a dead row), a row's tokens contiguous and in order,
    every tile of tq tokens one row's; state [R, H / pack, N, pack x P]
    float32 (`state_shape`): what each row starts the launch from (donated:
    the states after come back in its buffer), but zeros for a row where
    zero [R] bool holds.
    Returns (y [W, H, P] float32 WITHOUT the D x skip, the rows' states
    after the launch: a row with no token keeps its own, untouched)."""
    W, H, P = x.shape
    R, G, N, L = state.shape
    live, rix, first, count, starts = row_spans(tok_row, R)
    dt = jnp.where(live[:, None], dt.astype(F32), 0.0)
    cum = _segmented_cumsum(dt * A[None, :], starts)  # [W, H], <= 0
    flat = jnp.arange(W, dtype=jnp.int32)
    last = jnp.maximum(first + count - 1, 0)  # [R]
    cum_last = jnp.where((count > 0)[:, None], cum[jnp.minimum(last, W - 1)],
                         0.0)  # [R, H]
    xdt = jnp.where(live[:, None, None], x.astype(F32) * dt[:, :, None], 0.0)
    Bf = jnp.where(live[:, None], B.astype(F32), 0.0)
    Cf = jnp.where(live[:, None], C.astype(F32), 0.0)
    # within the launch: (C_w . B_u) exp(cum_w - cum_u) for u <= w of the
    # same row
    same = (live[:, None] & live[None, :]
            & (tok_row[:, None] == tok_row[None, :])
            & (flat[:, None] >= flat[None, :]))
    g = jnp.einsum("wn,un->wu", Cf, Bf, precision=_HI)
    diff = cum.T[:, :, None] - cum.T[:, None, :]  # [H, W, W]
    a = jnp.where(same[None], jnp.exp(jnp.minimum(diff, 0.0)) * g[None], 0.0)
    y = jnp.einsum("hwu,uhp->whp", a, xdt, precision=_HI)
    # across launches, a row's state moved once: exp(cum_w) C_w S_start, and
    # the state after: exp(cum_last) S_start + sum_u exp(cum_last - cum_u)
    # dt_u x_u B_u^T
    pad = ((0, -W % 8), (0, 0))
    # (a dead token's cum is no row's: its exponent is masked, not 0 x inf)
    left = jnp.where(live[:, None], cum_last[rix] - cum, 0.0)
    xd = xdt * jnp.exp(jnp.minimum(left, 0.0))[:, :, None]  # [W, H, P]
    xd = jnp.pad(xd.reshape(W, G, L).transpose(1, 0, 2),
                 ((0, 0),) + pad)
    keep = jnp.repeat(jnp.exp(cum_last), P, axis=1).reshape(R, G, L)
    read, state = ssm_scan(
        jnp.pad(Cf, pad), jnp.pad(Bf, pad), xd, keep, state, first, count,
        jnp.zeros((R,), jnp.int32) if zero is None else zero.astype(jnp.int32),
        tq=tq, interpret=resolve_interpret(interpret))
    read = read[:, :W].transpose(1, 0, 2).reshape(W, H, P)
    y = y + read * jnp.exp(jnp.minimum(cum, 0.0))[:, :, None]
    return jnp.where(live[:, None, None], y, 0.0), state


def causal_conv_rows(x, w, bias, start, tok_row):
    """A causal depthwise convolution over a launch's flat tokens: x [W, C]
    (the parameter dtype), w [K, C] taps (w[K - 1] the token's own), bias
    [C] or None, start [R, K - 1, C] the K - 1 inputs before each row's
    first token of the launch, oldest first (zeros for a row at position
    0). A row's tokens lie side by side on the flat axis, so token w's j-th
    predecessor is flat token w - j where that is the same row's and else
    comes from `start`: the row boundary decides, never the flat index.
    Returns (float32 [W, C] before any activation, the rows' last K - 1
    inputs after the launch [R, K - 1, C]: a row with no token keeps
    `start`'s)."""
    W, K = x.shape[0], w.shape[0]
    R = start.shape[0]
    live, rix, first, count, _ = row_spans(tok_row, R)

    def back(a, j):  # a[w - j], zeros before the axis' start
        return jnp.pad(a, ((j, 0), (0, 0)))[:W]

    flat = jnp.arange(W, dtype=jnp.int32)
    dist = jnp.minimum(flat - first[rix], K - 1)  # predecessors in the launch
    # prev[j - 1][w] = the input j tokens before token w
    prev = [
        jnp.where((live & (dist >= j))[:, None], back(x, j),
                  start[rix, jnp.clip(K - 1 - j + dist, 0, K - 2)]
                  .astype(x.dtype))
        for j in range(1, K)
    ]
    window = prev[::-1] + [x]
    wf = w.astype(F32)
    out = sum(wf[j] * z.astype(F32) for j, z in enumerate(window))
    if bias is not None:
        out = out + bias.astype(F32)
    at = jnp.clip(first + count - 1, 0, W - 1)  # a row's last token
    history = jnp.stack([z[at] for z in window[1:]], axis=1)  # [R, K-1, C]
    new = jnp.where((count > 0)[:, None, None], history,
                    start.astype(x.dtype))
    return out, new.astype(start.dtype)
