"""Decayed linear attention (Lightning Attention's form) over a float32
matrix state a row and head.

Per head h with decay a_h = exp(-s_h) and a row's tokens t = 0, 1, ...:

    S_t = a_h S_{t-1} + k_t^T v_t        (S: [Dh, Dh], float32)
    o_t = q_t S_t

`linear_attend_rows` computes the same sums for a paged launch's FLAT
tokens chunk by chunk: the launch is one chunk. Within it a masked
`Q K^T` weighted by the decay's powers (a token sees its own row's earlier
tokens of the launch, which lie side by side on the flat axis), in XLA;
across launches the carried state, in one Pallas program (`_scan_kernel`)
over (head group, the rows that carry a token): a row's state comes into
VMEM once, the row's tokens are read against it and folded into it, and it
goes back to where it came from. The state leaf is the program's aliased
output, so a row with no token of the launch costs no byte. A decode step
(one token a row) is the same call. tests/test_sala_ops.py holds both
against the recurrence itself, token by token.

The products that read or write S run at `Precision.HIGHEST`: S is stated
float32, and a one-pass bfloat16 product would round it at every read. The
slopes s_h = 2^(-8 h / H), h = 1 .. H, are the Lightning Attention family's
fixed ones (assumed: cellbench/configs/minicpm-sala-9b-16l.json)."""

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


def decay_slopes(n_heads: int) -> jnp.ndarray:
    """s_h [H] float32: the decay of head h (1-based) is exp(-s_h)."""
    h = jnp.arange(1, n_heads + 1, dtype=F32)
    return jnp.exp2(-8.0 * h / n_heads)


def _scan_heads(H: int, Wp: int, Dh: int) -> int:
    """Heads a program of the scan holds: the most (a program's fixed cost
    is paid once a group and row) whose blocks fit `_SCAN_VMEM_BYTES`: the
    group's q, k, v and output tokens and a row's state in and out, two
    buffers each. A whole tile of sublanes, or every head."""
    for Hg in range(H, 0, -1):
        if H % Hg or (Hg % 8 and Hg != H):
            continue
        if 2 * 4 * Hg * (4 * Wp * Dh + 2 * Dh * Dh) <= _SCAN_VMEM_BYTES:
            return Hg
    return H


def _scan_kernel(rows_ref, n_ref, first_ref, count_ref, qd_ref, kd_ref,
                 v_ref, keep_ref, s_in_ref, o_ref, s_out_ref, cols, *,
                 Hg: int, Tb: int, Wp: int, align: int):
    """One program per (head group g, place j): the j-th row that carries a
    token (rows_ref[j]; past the n_ref[0] rows that do, the last one's
    blocks stay where they are and nothing runs). qd / kd / v [Hg, Wp, Dh]
    float32: the launch's tokens, q and k under their decays (module
    docstring), zeros where a token is dead; the row's are first_ref[row]
    .. + count_ref[row]. keep [1, Hg, Dh]: a^count. Writes the row's
    tokens' read of the state it starts from into o [Hg, Wp, Dh] (every
    other token zeros) and the state after the launch."""
    j = pl.program_id(1)
    n = n_ref[0]
    row = rows_ref[j]
    first, count = first_ref[row], count_ref[row]

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((j == 0) & (n == 0))
    def _():  # no token at all: the block this program holds goes back
        s_out_ref[...] = s_in_ref[...]

    @pl.when((j < n) & (count == 1))
    def _():
        # one token: the recurrence on the vector unit (a [1, Dh] x
        # [Dh, Dh] product would load the array's weights a head for one
        # row). q and k as columns: the heads' rows transposed together.
        at = pl.ds(first, 1)
        for h in range(Hg):
            cols[h:h + 1, :] = qd_ref[h, at, :]
            cols[Hg + h:Hg + h + 1, :] = kd_ref[h, at, :]
        t = cols[...].T
        for h in range(Hg):
            S = s_in_ref[0, h]
            o_ref[h, at, :] = jnp.sum(t[:, h:h + 1] * S, axis=0,
                                      keepdims=True)
            s_out_ref[0, h] = (keep_ref[0, h:h + 1, :] * S
                               + t[:, Hg + h:Hg + h + 1] * v_ref[h, at, :])

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

            def head(h, carry):
                S = s_in_ref[0, h]
                read = jnp.dot(qd_ref[h, at, :], S, precision=_HI,
                               preferred_element_type=F32)
                o_ref[h, at, :] = jnp.where(mine, read, o_ref[h, at, :])
                kd = jnp.where(mine, kd_ref[h, at, :], 0.0)
                # (the first block starts the state after: a^count S)
                was = jnp.where(b == 0, keep_ref[0, pl.ds(h, 1), :] * S,
                                s_out_ref[0, h])
                s_out_ref[0, h] = was + jax.lax.dot_general(
                    kd, v_ref[h, at, :], (((0,), (0,)), ((), ())),
                    precision=_HI, preferred_element_type=F32)
                return carry

            # (a loop, not Hg copies of the body: a step program traces and
            # lowers the kernel at every start, compile cache or not)
            return jax.lax.fori_loop(0, Hg, head, carry)

        jax.lax.fori_loop(0, (count + Tb - 1) // Tb, block, 0)


@functools.partial(jax.jit, static_argnames=("tq", "interpret"))
def linear_scan(qd, kd, v, keep, state, first, count, *, tq: int, interpret):
    """The pallas_call: qd, kd, v [H, Wp, Dh] float32, keep [R, H] float32,
    state [R, H, Dh, Dh] float32 (donate it: the output's buffer), first /
    count [R] int32. Returns (o [H, Wp, Dh], the state after). Jitted, so
    that a stack's layers trace and lower ONE kernel a step program (a
    second a layer was a second of every start, compile cache or not)."""
    H, Wp, Dh = qd.shape
    R = state.shape[0]
    Hg = _scan_heads(H, Wp, Dh)
    Tb = min(_SCAN_TOKENS, Wp)
    # the rows that carry a token first, in order; every place past them
    # names the last of them, whose blocks then neither move nor change
    touched = count > 0
    n = jnp.sum(touched.astype(jnp.int32))
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(R), jnp.maximum(n - 1, 0))]

    def tokens():
        return pl.BlockSpec((Hg, Wp, Dh), lambda g, j, *refs: (g, 0, 0))

    def of_row(*tail):
        return pl.BlockSpec(
            (1, Hg) + tail,
            lambda g, j, rows, *refs: (rows[j], g) + (0,) * len(tail))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // Hg, R),
        in_specs=[tokens(), tokens(), tokens(), of_row(Dh), of_row(Dh, Dh)],
        out_specs=[tokens(), of_row(Dh, Dh)],
        scratch_shapes=[pltpu.VMEM((max(128, 2 * Hg), Dh), F32)],
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, Hg=Hg, Tb=Tb, Wp=Wp,
                          align=8 if tq % 8 == 0 else 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(qd.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operands: 4 prefetched scalars, qd, kd, v, keep, the state
        input_output_aliases={8: 1},
        interpret=interpret,
    )(rows, jnp.reshape(n, (1,)), first, count, qd, kd, v,
      jnp.broadcast_to(keep[:, :, None], (R, H, Dh)), state)


@jax.named_scope("linear_scan")
def linear_attend_rows(q, k, v, state, tok_row, tq: int, interpret=None):
    """q, k, v [W, H, Dh]: a launch's flat tokens (q scaled); tok_row [W]
    int32 the fleet row of each (-1: launch padding, a dead row), a row's
    tokens contiguous and in order, every tile of tq tokens one row's;
    state [R, H, Dh, Dh] float32: what each row starts the launch from
    (donated: the states after come back in its buffer).
    Returns (o [W, H, Dh] float32, the rows' states after the launch: a
    row with no token keeps its own, untouched)."""
    W, H, Dh = q.shape
    R = state.shape[0]
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
    o = jnp.einsum("hwu,uhd->hwd", a, v.astype(F32), precision=_HI)
    # across launches, a row's state moved once: q_w a^(i + 1) S_start, and
    # the state after: a^n S_start + sum_w a^(n - 1 - i_w) k_w^T v_w

    def heads_first(x, power=None):
        x = x.astype(F32)
        if power is not None:
            x = x * jnp.exp(-s[None, :, None] * power[:, None, None])
        x = jnp.where(live[:, None, None], x, 0.0).transpose(1, 0, 2)
        return jnp.pad(x, ((0, 0), (0, -W % 8), (0, 0)))

    keep = jnp.exp(-s[None, :] * count.astype(F32)[:, None])  # [R, H]
    read, state = linear_scan(
        heads_first(q, i + 1.0), heads_first(k, n - 1.0 - i), heads_first(v),
        keep, state, first, count, tq=tq,
        interpret=resolve_interpret(interpret))
    o = (o + read[:, :W]).transpose(1, 0, 2)
    return jnp.where(live[:, None, None], o, 0.0), state
