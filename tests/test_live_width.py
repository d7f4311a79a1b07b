"""A mixed step computes the tokens it carries (ISSUE 54, and every padded
fleet since ISSUE 56): the launch's live flat tokens packed side by side on
an axis of `live_width` (engine/paged.live_tokens; only the paged hook's
kernel and pool write see the tile layout) against the SAME launches at
`live_width == width`, the tile layout throughout: the emitted tokens, the
slot state and every leaf of the pool after a run of launches that starts
rows cold, decodes beside prefill, carries no prefill at all, and fills the
compact axis exactly. At `test-granite-tiny` (float32 matrix and convolution
states, both snapshot pools: a snapshot taken and restored), `test-mimo-tiny`
under a held expert share (a pool grouped by layer kind, a learned sink: both
groups' leaves and the routed counts) and `test-olmo2-tiny` (K/V alone); K/V
outside the trash block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.config import resolve_attn_impl
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models.registry import get_model_config

SLOTS, TILE, BS, MB = 8, 8, 8, 12
WIDTH = SLOTS * TILE + 32  # the fleet's tiles and a budget on top: 96
LIVE = 40  # the compact axis


def ids(n, salt):
    return np.random.default_rng(100 * salt + n).integers(3, 250, n).astype(np.int32)


# Each launch: (decode rows, [(row, start, tokens, kind, completes)],
# {row: snapshot restored}, {row: snapshot taken}); rows 6 and 7 decode from
# the start (armed by hand), the others are armed by their last chunk.
LAUNCHES = (
    # three rows start cold; row 0's chunk ends on a block: a snapshot taken
    ([6, 7], [(0, 0, ids(16, 0), P.RAGGED_FIRST, False),
              (1, 0, ids(13, 1), P.RAGGED_FIRST, True),
              (2, 0, ids(5, 2), P.RAGGED_FIRST, True)], {}, {0: 1}),
    # row 3 starts from that snapshot (a prefix hit at 16) beside a chunk that
    # goes on; rows 1 and 2 decode now
    ([1, 2, 6, 7], [(0, 16, ids(11, 3), P.RAGGED_PREFILL, True),
                    (3, 16, ids(9, 4), P.RAGGED_FIRST, False)], {3: 1}, {}),
    # no prefill at all
    ([0, 1, 2, 6, 7], [], {}, {}),
    # exactly LIVE tokens: 5 decode rows and 35 prompt tokens, a slot let
    # again from zeros (row 4 held nothing; row 5 cold too)
    ([0, 1, 2, 6, 7], [(3, 25, ids(8, 5), P.RAGGED_PREFILL, True),
                       (4, 0, ids(16, 6), P.RAGGED_FIRST, False),
                       (5, 0, ids(11, 7), P.RAGGED_FIRST, True)], {}, {4: 2}),
    ([0, 1, 2, 3, 5, 6, 7], [(4, 16, ids(3, 8), P.RAGGED_PREFILL, True)], {}, {}),
)


# model -> (its configuration, the pool's leaves that are compared)
MODELS = {
    "test-granite-tiny": (lambda: get_model_config("test-granite-tiny"),
                          ("lin", "snap", "conv", "csnap", "k", "v")),
    "test-mimo-tiny": (lambda: get_model_config("test-mimo-tiny").replace(
        name="test-mimo-share", expert_lo=2, n_experts_held=4),
        ("k", "v", "kw", "vw", "routed")),
    "test-olmo2-tiny": (lambda: get_model_config("test-olmo2-tiny"), ("k", "v")),
}
CASES = [(model, impl) for model in MODELS for impl in ("xla", "pallas")]


def run(model, impl, live_width):
    """The launches in order through `mixed_step_ragged`; returns ([packed],
    the slot state, the pool)."""
    cfg = resolve_attn_impl(MODELS[model][0](), impl)
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    groups = len(cfg.kv_groups)
    blocks = SLOTS * MB + 1
    pool = P.init_pool(cfg, blocks if groups == 1 else (blocks,) * groups, BS,
                       n_slots=SLOTS, n_snapshots=4)
    # (a grouped pool's launch table: its groups' side by side, every row
    # its own blocks of each)
    table = jnp.asarray(np.tile(
        1 + np.arange(SLOTS * MB, dtype=np.int32).reshape(SLOTS, MB), (1, groups)))
    stateful = bool(cfg.linear_layers)
    state, sparams = G.init_slots(SLOTS, cfg.vocab_size)
    on = np.zeros((SLOTS,), bool)
    on[[6, 7]] = True
    state = state._replace(
        token=jnp.where(on, 9, 0).astype(jnp.int32), pos=jnp.where(on, 20, 0).astype(jnp.int32),
        active=jnp.asarray(on), remaining=jnp.where(on, 64, 0).astype(jnp.int32))
    key = jax.random.PRNGKey(0)
    packed = []
    for decode, chunks, restore, take in LAUNCHES:
        entries = [(b, 0, 1, P.RAGGED_DECODE) for b in decode] + [
            (row, start, len(toks), kind if stateful else P.RAGGED_PREFILL)
            for row, start, toks, kind, _ in chunks]
        meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
            entries, width=WIDTH, tile=TILE)
        assert (tok_row >= 0).sum() <= LIVE
        toks = np.zeros((WIDTH,), np.int32)
        dec_flag = np.zeros((WIDTH,), bool)
        dec_idx = np.zeros((SLOTS,), np.int32)
        for b, off in zip(decode, offsets):
            dec_flag[off], dec_idx[b] = True, off
        arm = P.idle_mixed_arm(SLOTS, cfg.vocab_size)
        arm_on, arm_idx, plen = (np.zeros((SLOTS,), t) for t in (bool, np.int32, np.int32))
        for (row, start, ctoks, _, completes), off in zip(chunks, offsets[len(decode):]):
            toks[off:off + len(ctoks)] = ctoks
            if completes:
                arm_on[row], arm_idx[row] = True, off + len(ctoks) - 1
                plen[row] = start + len(ctoks)
        arm = arm._replace(on=jnp.asarray(arm_on), idx=jnp.asarray(arm_idx),
                           prompt_len=jnp.asarray(plen),
                           max_tokens=jnp.full((SLOTS,), 64, jnp.int32))
        snaps = np.full((2, SLOTS), -1, np.int32)
        for row, index in restore.items():
            snaps[0, row] = index
        for row, index in take.items():
            snaps[1, row] = index
        dev = P.DeviceMeta(*map(jnp.asarray, P.build_device_meta(
            entries, offsets, len(decode), width=WIDTH, tile=TILE)))
        out, state, sparams, pool = P.mixed_step_ragged(
            cfg, params, jnp.asarray(toks), jnp.asarray(tok_row), jnp.asarray(tok_pos),
            jnp.asarray(dec_flag), jnp.asarray(meta), pool, table, state, sparams, key,
            jnp.asarray(dec_idx), arm, dev=dev,
            **({"snaps": (jnp.asarray(snaps[0]), jnp.asarray(snaps[1]))} if stateful else {}),
            **({} if live_width is None else {"live_width": live_width}))
        packed.append(np.asarray(out))
    return packed, state, pool


_RUNS = {}


def both(model, impl):
    """(the tile layout's run, the compact axis's), made once a case."""
    if (model, impl) not in _RUNS:
        _RUNS[model, impl] = run(model, impl, None), run(model, impl, LIVE)
    return _RUNS[model, impl]


def test_launches_fill_the_compact_axis_exactly_and_not_at_all():
    live = [len(d) + sum(len(c[2]) for c in chunks) for d, chunks, _, _ in LAUNCHES]
    assert max(live) == LIVE and LIVE < WIDTH
    assert any(not chunks for _, chunks, _, _ in LAUNCHES)


@pytest.mark.parametrize("model,impl", CASES)
def test_emitted_tokens_and_slot_state_equal(model, impl):
    """(a routed model's packed rows end in its experts' counts: equal too)"""
    (packed_t, state_t, _), (packed_c, state_c, _) = both(model, impl)
    for n, (a, b) in enumerate(zip(packed_t, packed_c)):
        np.testing.assert_array_equal(a, b, err_msg=f"launch {n}")
    assert int(np.asarray(packed_t[-1][2]).sum()) == 8  # every row decodes by the end
    for name in ("token", "pos", "active", "remaining"):
        np.testing.assert_array_equal(getattr(state_t, name), getattr(state_c, name), err_msg=name)


@pytest.mark.parametrize("model,impl,leaf", [
    (model, impl, leaf) for model, impl in CASES for leaf in MODELS[model][1]])
def test_pool_leaves_equal(model, impl, leaf):
    """Every leaf to 1e-5 + 1e-5 of the value after five launches, in a
    float32 model (a row's tokens lie elsewhere on a shorter axis, so the
    segmented running sum's tree and the within-launch sums add the same
    numbers in another order: 1.5e-6 on a matrix state's 0.54 and 1.2e-6 on
    a value row's 0.013 were the most read), snapshots taken and restored
    among them; K/V of every group outside the trash block (dead tokens
    write there, and which of them wrote last is the layout's); the routed
    counts of the last launch to the pair."""
    (_, _, pool_t), (_, _, pool_c) = both(model, impl)
    a, b = pool_t[leaf], pool_c[leaf]
    if leaf == "routed":
        assert int(np.asarray(a).sum()) > 0
        np.testing.assert_array_equal(a, b)
        return
    if leaf in ("k", "v", "kw", "vw"):
        a, b = (a[:, 1:],), (b[:, 1:],)
    assert len(a) == len(b) > 0
    for layer, (x, y) in enumerate(zip(a, b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        assert np.abs(x).max() > 0, (leaf, layer)
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5, err_msg=f"{leaf} layer {layer}")


def test_live_tokens_keeps_flat_order_and_rows_contiguous():
    tok_row = np.full((48,), -1, np.int32)
    tok_row[0], tok_row[8], tok_row[16:27], tok_row[32:35] = 4, 2, 5, 1
    at, back = map(np.asarray, P.live_tokens(jnp.asarray(tok_row), 24))
    live = np.flatnonzero(tok_row >= 0)
    assert list(at[:len(live)]) == list(live)
    assert (tok_row[at[len(live):]] == -1).all()  # the rest: launch padding
    assert list(back[live]) == list(range(len(live)))
    assert back.min() >= 0 and back.max() < 24
