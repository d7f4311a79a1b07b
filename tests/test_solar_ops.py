"""ops/delta_rule.py against the recurrence itself, token by token in
float64, at tiny sizes on the CPU: the launch form of the gated delta rule
(the chunk algebra in XLA, the carried state's interpreted kernel or its XLA
twin) for one row and for several rows ragged on one flat axis, with beta on
both sides of 1, the fastest and the slowest decays `A_log` / `dt_bias` can
give, a row spread over several launches, a row re-let from zeros; and the
decode program's one-token form (`delta_rule_step`: the interpreted kernel or
its jax.numpy twin) against the same recurrence, alone, beside the chunked
form of the same launch, and handed a chunked launch's state and back.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.ops import delta_rule as DR

TOL = 2e-4


def _recurrence(q, k, v, g, beta, S0):
    """One row's tokens q, k [n, H, Dk], v [n, H, Dv], g [n, H, Dk], beta
    [n, H] from the state S0 [H, Dk, Dv], one at a time in float64."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, S0))
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, :, None] * S
        err = v[t] - np.einsum("hd,hdv->hv", k[t], S)
        S = S + beta[t][:, None, None] * k[t][:, :, None] * err[:, None, :]
        out.append(np.einsum("hd,hdv->hv", q[t], S))
    return np.stack(out), S


# a launch's flat axis: (tile, [(fleet row or -1 for a tile of launch padding,
# its tokens)]), 4 fleet rows (tests/test_granite_ops.py's table)
LAUNCHES = {
    "one-row": (8, [(0, 21)]),
    "tq1": (1, [(0, 12), (-1, 0), (2, 1), (3, 8)]),
    "tq4": (4, [(0, 12), (-1, 0), (2, 4), (3, 8)]),
    # a row over four chunks beside rows of one token, the first not at a
    # chunk's start
    "tiles-of-8": (8, [(2, 1), (0, 200), (-1, 0), (3, 1)]),
    "decode": (1, [(0, 1), (1, 1), (2, 1), (3, 1)]),
    # live tokens packed side by side: rows of any length share a sub-chunk
    "compact": (1, [(2, 1), (3, 1), (0, 140)] + [(-1, 0)] * 11),
}
# (beta's range, the log decay a token's range): the slowest is dt 0.001 at
# a = 1, the fastest softplus(3) at a = 16; "mixed" draws a channel's from
# the whole range, so neighbours in one sub-chunk differ by e^-48 a token
REGIMES = {
    "slow-small-beta": ((0.05, 0.95), (-1e-3, -1e-3)),
    "fast-large-beta": ((1.05, 1.98), (-48.0, -30.0)),
    "mixed": ((0.0, 2.0), (-48.0, -1e-3)),
    "typical": ((0.2, 1.8), (-0.5, -0.01)),
}


def _flat(tile, spans):
    tok_row = []
    for row, n in spans:
        tiles = max(1, -(-n // tile))
        tok_row += [row] * n + [-1] * (tiles * tile - n)
    return np.asarray(tok_row, np.int32)


def _draw(seed, W, H, Dk, Dv, regime, repeat_keys=False):
    (b_lo, b_hi), (g_lo, g_hi) = REGIMES[regime]
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((W, H, Dk)) * Dk ** -0.5
    k = rng.standard_normal((W, H, Dk))
    if repeat_keys:  # neighbours nearly alike: A's entries near beta
        k = k[:1] + 0.05 * k
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((W, H, Dv))
    beta = rng.uniform(b_lo, b_hi, (W, H))
    if regime == "mixed":  # log-uniform over the whole range
        g = -np.exp(rng.uniform(np.log(-g_hi), np.log(-g_lo), (W, H, Dk)))
    else:
        g = rng.uniform(g_lo, g_hi, (W, H, Dk))
    return tuple(np.asarray(a, np.float32) for a in (q, k, v, g, beta))


def _launch(args, state, tok_row, tile, impl, zero=None):
    return DR.delta_rule_rows(
        *map(jnp.asarray, args), jnp.asarray(state), jnp.asarray(tok_row),
        tile, zero=None if zero is None else jnp.asarray(zero), impl=impl)


def _every(R):
    return np.arange(R, dtype=np.int32)


def _step(args, state, tok_row, impl):
    """The one-token form: flat place i is fleet row i's (or nobody's)."""
    return DR.delta_rule_step(
        *map(jnp.asarray, args), jnp.asarray(state), jnp.asarray(tok_row),
        impl=impl)


# a fleet of 4 (or 5) rows at one decode step: which rows carry a token
STEPS = {
    "all-rows": [0, 1, 2, 3],
    "inactive-rows": [-1, 1, -1, 3, -1],
    "last-row-alone": [-1, -1, -1, 3],
    "no-row": [-1, -1, -1, -1],
}


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("name", list(STEPS))
def test_the_one_token_form_is_the_recurrence(name, regime, impl):
    """A decode step's o and state after against the float64 recurrence in
    every regime of `_draw` (decays to e^-48 a token and channel, beta to
    1.98); a row with no token reads zeros and its state block comes back bit
    for bit, wherever it lies among the rows that carry one."""
    tok_row = np.asarray(STEPS[name], np.int32)
    R, H, Dk, Dv = len(tok_row), 2, 32, 16
    args = _draw(len(name) + len(regime), R, H, Dk, Dv, regime)
    S0 = np.random.default_rng(5).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32)
    o, S1 = _step(args, S0, tok_row, impl)
    _check_rows(args, S0, tok_row, o, S1)


def _check_rows(args, S0, tok_row, o, S1, zero=()):
    """Every row against the recurrence from its own start state (stored
    transposed: [R, H, Dv, Dk])."""
    o, S1 = np.asarray(o), np.asarray(S1)
    assert np.isfinite(o).all() and np.isfinite(S1).all()
    for r in range(S0.shape[0]):
        at = np.flatnonzero(tok_row == r)
        if not len(at):
            np.testing.assert_array_equal(S1[r], S0[r])
            continue
        assert (np.diff(at) == 1).all()
        start = np.zeros_like(S0[r]) if r in zero else S0[r]
        want, S = _recurrence(*(a[at] for a in args),
                              start.transpose(0, 2, 1))
        scale = max(1.0, np.abs(want).max())
        assert np.abs(o[at] - want).max() <= TOL * scale, (r, "o")
        S = S.transpose(0, 2, 1)
        assert np.abs(S1[r] - S).max() <= TOL * max(1.0, np.abs(S).max()), \
            (r, "S")
    assert not o[tok_row < 0].any()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("name", list(LAUNCHES))
def test_the_launch_form_of_the_delta_rule_is_the_recurrence(name, regime,
                                                             impl):
    """o and the state after, a row at a time, against the float64
    recurrence: nothing overflows at e^-48 a token and channel, a state that
    decays to nothing is the recurrence's own zero, rows with no token keep
    their state bit for bit, dead tokens read zeros."""
    tile, spans = LAUNCHES[name]
    tok_row = _flat(tile, spans)
    H, Dk, Dv, R = 2, 32, 16, 4
    args = _draw(len(name) + len(regime), len(tok_row), H, Dk, Dv, regime)
    S0 = np.random.default_rng(5).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32)
    o, S1 = _launch(args, S0, tok_row, tile, impl)
    _check_rows(args, S0, tok_row, o, S1)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_keys_that_repeat_do_not_cancel_in_the_solve(impl):
    """Neighbouring keys nearly alike, beta near 2 and no decay to speak of:
    (I + A)'s strict triangle is full of entries near 2, and the inverse by
    forward substitution still holds the bound (the product form loses four
    digits here)."""
    tok_row = _flat(8, [(0, 128), (1, 64)])
    args = _draw(3, len(tok_row), 2, 32, 16, "slow-small-beta",
                 repeat_keys=True)
    args = args[:4] + (np.full_like(args[4], 1.9),)
    S0 = np.zeros((2, 2, 16, 32), np.float32)
    o, S1 = _launch(args, S0, tok_row, 8, impl)
    _check_rows(args, S0, tok_row, o, S1)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_keys_that_repeat_step_after_step_are_the_recurrence(impl):
    """The same keys a token at a time: 24 decode steps of two rows whose
    keys are nearly one key, beta 1.9 and no decay to speak of (every step
    reflects the state about almost the same direction), against one pass of
    the recurrence."""
    R, H, Dk, Dv, steps = 2, 2, 32, 16, 24
    args = _draw(3, steps * R, H, Dk, Dv, "slow-small-beta", repeat_keys=True)
    args = args[:4] + (np.full_like(args[4], 1.9),)
    args = tuple(a.reshape((steps, R) + a.shape[1:]) for a in args)
    S0 = np.zeros((R, H, Dv, Dk), np.float32)
    _check_steps(args, S0, [
        ("step", t, _every(R)) for t in range(steps)], impl)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("cuts", [(64, 64, 64), (37, 90, 1, 12), (1, 1, 1, 130)])
def test_a_row_spread_over_launches_is_one_row(cuts, impl):
    """A row's tokens in several launches, the state carried between them
    (and a second row that rides only the first launch), against ONE pass of
    the recurrence over all of them."""
    H, Dk, Dv, R = 2, 32, 16, 3
    n = sum(cuts)
    args = _draw(11, n, H, Dk, Dv, "typical")
    S0 = np.random.default_rng(6).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32)
    want, S_end = _recurrence(*args, S0[1].transpose(0, 2, 1))
    other = _draw(12, 5, H, Dk, Dv, "typical")
    state, done, outs = jnp.asarray(S0), 0, []
    for i, c in enumerate(cuts):
        part = tuple(a[done:done + c] for a in args)
        tok_row = np.full((c,), 1, np.int32)
        if i == 0:  # row 2's five tokens first, then this row's
            part = tuple(np.concatenate([b, a]) for a, b in zip(part, other))
            tok_row = np.concatenate([np.full((5,), 2, np.int32), tok_row])
        o, state = _launch(part, state, tok_row, 1, impl)
        outs.append(np.asarray(o)[-c:])
        done += c
    got = np.concatenate(outs)
    assert np.abs(got - want).max() <= TOL * max(1.0, np.abs(want).max())
    S_end = S_end.transpose(0, 2, 1)
    assert np.abs(np.asarray(state)[1] - S_end).max() <= TOL * max(
        1.0, np.abs(S_end).max())
    np.testing.assert_array_equal(np.asarray(state)[0], S0[0])


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_row_let_again_starts_from_zeros(impl):
    """`zero`: the row's block is read as zeros, whatever the previous
    tenant left in it; the other rows start from their own."""
    tile, spans = 4, [(0, 12), (1, 70), (3, 1)]
    tok_row = _flat(tile, spans)
    H, Dk, Dv, R = 2, 32, 16, 4
    args = _draw(21, len(tok_row), H, Dk, Dv, "typical")
    S0 = np.random.default_rng(7).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32) * 10
    zero = np.array([False, True, False, False])
    o, S1 = _launch(args, S0, tok_row, tile, impl, zero=zero)
    _check_rows(args, S0, tok_row, o, S1, zero=(1,))


def _check_steps(args, S0, plan, impl):
    """args [steps, R, ...]: a token a row and step. plan: [(form, step,
    tok_row [R])], form "step" the one-token form, "rows" the chunked form of
    the same one-token-a-row launch; the state carried from call to call.
    Every row against ONE pass of the recurrence over the steps it rode."""
    R = S0.shape[0]
    state, outs = jnp.asarray(S0), []
    for form, t, tok_row in plan:
        part = tuple(a[t] for a in args)
        o, state = _step(part, state, tok_row, impl) if form == "step" \
            else _launch(part, state, tok_row, 1, impl)
        outs.append(np.asarray(o))
    got = np.stack(outs)  # [len(plan), R, H, Dv]
    for r in range(R):
        rode = [i for i, (_, _, tok_row) in enumerate(plan) if tok_row[r] >= 0]
        at = [plan[i][1] for i in rode]
        assert not got[[i for i in range(len(plan)) if i not in rode], r].any()
        if not rode:
            np.testing.assert_array_equal(np.asarray(state)[r], S0[r])
            continue
        want, S = _recurrence(*(a[at, r] for a in args),
                              S0[r].transpose(0, 2, 1))
        assert np.abs(got[rode, r] - want).max() <= TOL * max(
            1.0, np.abs(want).max()), r
        S = S.transpose(0, 2, 1)
        assert np.abs(np.asarray(state)[r] - S).max() <= TOL * max(
            1.0, np.abs(S).max()), r


# which form serves each of sixteen one-token-a-row steps of three rows
HANDOVERS = {
    # the decode program alone
    "steps": lambda t: "step",
    # the same launches through the chunked form (a mixed launch's decode
    # rows): what the one-token form must agree with
    "rows": lambda t: "rows",
    # a mixed launch between decode chunks, and a decode chunk between mixed
    # launches: each form reads the state the other wrote
    "rows-then-steps": lambda t: "rows" if t < 5 else "step",
    "steps-then-rows": lambda t: "step" if t < 11 else "rows",
    "turn-about": lambda t: ("step", "rows")[(t // 3) % 2],
}


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("name", list(HANDOVERS))
def test_a_decode_step_is_the_recurrence(name, impl):
    """One token a row for sixteen steps, the state carried from call to
    call, at the published head width: the recurrence over the sixteen,
    whichever form serves a step (row 1 sits out steps 4 to 6: its state
    waits, bit for bit, in either form's launch)."""
    H, Dk, Dv, R, steps = 2, 128, 128, 3, 16
    args = _draw(31, steps * R, H, Dk, Dv, "typical")
    args = tuple(a.reshape((steps, R) + a.shape[1:]) for a in args)
    S0 = np.random.default_rng(8).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32)
    plan = [(HANDOVERS[name](t), t,
             np.where((_every(R) == 1) & (4 <= t <= 6), -1, _every(R)))
            for t in range(steps)]
    _check_steps(args, S0, plan, impl)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("regime", list(REGIMES))
def test_the_two_forms_of_a_one_token_launch_agree(regime, impl):
    """The SAME one-token-a-row launch through the one-token form and through
    the chunked form: o and the state after agree to `TOL` (each is held to
    the float64 recurrence above; this holds them to each other), and a row
    with no token keeps its block in both."""
    R, H, Dk, Dv = 4, 2, 128, 128
    tok_row = np.asarray([0, -1, 2, 3], np.int32)
    args = _draw(41, R, H, Dk, Dv, regime)
    S0 = np.random.default_rng(9).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32)
    o_step, S_step = map(np.asarray, _step(args, S0, tok_row, impl))
    o_rows, S_rows = map(np.asarray, _launch(args, S0, tok_row, 1, impl))
    assert np.abs(o_step - o_rows).max() <= TOL * max(1.0, np.abs(o_rows).max())
    assert np.abs(S_step - S_rows).max() <= TOL * max(1.0, np.abs(S_rows).max())
    np.testing.assert_array_equal(S_step[1], S0[1])
    np.testing.assert_array_equal(S_rows[1], S0[1])


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_prompt_chunk_then_decode_steps_then_a_chunk_is_one_row(impl):
    """The hand-over as the engine makes it: a 70-token chunk through the
    chunked form (beside another row's), eight decode steps through the
    one-token form, a 9-token chunk through the chunked form again, and two
    more steps: one unbroken recurrence over the row's 89 tokens."""
    H, Dk, Dv, R = 2, 32, 16, 3
    cuts = [("rows", 70), ("step", 8), ("rows", 9), ("step", 2)]
    n = sum(c for _, c in cuts)
    args = _draw(51, n, H, Dk, Dv, "typical")
    S0 = np.random.default_rng(6).standard_normal(
        (R, H, Dv, Dk)).astype(np.float32)
    want, S_end = _recurrence(*args, S0[1].transpose(0, 2, 1))
    state, done, outs = jnp.asarray(S0), 0, []
    for form, c in cuts:
        if form == "rows":
            part = tuple(a[done:done + c] for a in args)
            o, state = _launch(part, state, np.full((c,), 1, np.int32), 1, impl)
            outs.append(np.asarray(o))
        else:
            for t in range(done, done + c):
                part = tuple(np.stack([np.zeros_like(a[t]), a[t],
                                       np.zeros_like(a[t])]) for a in args)
                o, state = _step(part, state, np.asarray([-1, 1, -1], np.int32),
                                 impl)
                outs.append(np.asarray(o)[1:2])
        done += c
    got = np.concatenate(outs)
    assert np.abs(got - want).max() <= TOL * max(1.0, np.abs(want).max())
    S_end = S_end.transpose(0, 2, 1)
    assert np.abs(np.asarray(state)[1] - S_end).max() <= TOL * max(
        1.0, np.abs(S_end).max())
    np.testing.assert_array_equal(np.asarray(state)[[0, 2]], S0[[0, 2]])


def test_the_hosts_count_of_chunks_is_the_kernels():
    assert [DR.chunks_of(*a) for a in
            [(0, 0), (0, 1), (0, 64), (0, 65), (63, 2), (8, 448), (64, 448),
             (40, 472)]] == [0, 1, 1, 2, 2, 8, 7, 8]
