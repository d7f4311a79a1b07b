"""The solar_open2 family (models/solar_open2.py, ISSUE 57) against the plain
reference (cellbench/reference/delta_hybrid_moe.py) at `test-solar-tiny`,
seeded random weights, at the level of engine/paged's hooks: prefill then
decode through the pool, the logits themselves; the two trees leaf by leaf;
a share's forward; the published preset's sizes; and the table from a layer
KIND to what it keeps (config.STATE_OF_KIND), which the other recurrent
families' pools are read from too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import solar_util as U
from distributed_llm_inference_tpu.config import (KEEPS_CONV_STATE,
                                                  KEEPS_MATRIX_STATE,
                                                  STATE_OF_KIND, ModelConfig)
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models import solar_open2 as S
from distributed_llm_inference_tpu.models.registry import get_model_config

SEED, BS = 3, 8
CFG = get_model_config("test-solar-tiny")


def prompt_ids(n, salt=0):
    return [int(t) for t in np.random.default_rng(1000 * salt + n).integers(3, 250, n)]


def assert_logits(got, want, within=2e-3):
    assert np.abs(got - want).max() < within * want.std(), \
        (np.abs(got - want).max(), want.std())


def _serve(cfg, ids, cuts, decode, seed=SEED):
    """`ids` through the pool: the first sum(cuts) tokens as prefill chunks
    of `cuts` tokens, then `decode` tokens one a launch. Every token's
    logits, in order."""
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    pool = P.init_pool(cfg, 40, BS, n_slots=1, n_snapshots=1)
    table = np.zeros((1, 32), np.int32)
    table[0, :30] = np.arange(1, 31)
    out, at = [], 0
    for i, n in enumerate(list(cuts) + [1] * decode):
        kind = P.RAGGED_FIRST if i == 0 else (
            P.RAGGED_PREFILL if i < len(cuts) else P.RAGGED_DECODE)
        (lg,), pool = U.launch(cfg, params, pool, table,
                               [(0, at, ids[at:at + n], kind)], width=160)
        out.append(lg)
        at += n
    return np.concatenate(out), pool


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cuts", [(150,), (64, 64, 22), (21, 70, 3, 40)])
def test_prefill_then_decode_through_the_pool_is_the_references_forward(cuts, impl):
    """A prompt prefilled whole, in chunks that end on the delta rule's
    chunk boundaries, or ragged over them, then eight tokens decoded one a
    launch: every position's logits against the reference's full forward, at
    float32 rounding."""
    cfg = CFG.replace(attn_impl=impl)
    ids = prompt_ids(sum(cuts) + 8, 1)
    got, pool = _serve(cfg, ids, cuts, 8)
    assert_logits(got, U.ref_logits(cfg, SEED, ids))
    assert all(leaf.dtype == jnp.float32 for leaf in pool["lin"] + pool["snap"])


def test_in_bfloat16_the_served_choices_are_the_references():
    """bfloat16 weights and activations (the state stays float32): each
    position's choice lies, on average, within a small margin of the
    reference's best, in logit-sigmas (the harness's own measure)."""
    cfg = CFG.replace(dtype="bfloat16")
    ids = prompt_ids(96, 5)
    got, pool = _serve(cfg, ids, (40, 48), 8)
    want = U.ref_logits(cfg, SEED, ids, jnp.bfloat16)
    choice = got.argmax(-1)
    margin = (want.max(-1) - want[np.arange(len(ids)), choice]) / want.std()
    # (64 wide, 8 experts: a router near-tie that flips moves a row far more
    # than at the published widths, where the cell's check holds the limits)
    assert margin.mean() < 0.05 and (margin > 0).mean() < 0.2
    assert all(leaf.dtype == jnp.float32 for leaf in pool["lin"])
    assert all(leaf.dtype == jnp.bfloat16 for leaf in pool["conv"])


def test_the_two_trees_are_one_draw():
    """Every leaf of `init_params` is the reference's `make_params` leaf, bit
    for bit (the documented table of keys): w_in its six parts side by side."""
    params = M.init_params(CFG, jax.random.PRNGKey(SEED))
    ref = U.ref_params(CFG, SEED)
    layers = params["layers"]
    np.testing.assert_array_equal(params["embed"], ref["embed"])
    np.testing.assert_array_equal(params["head"].T, ref["lm_head"])
    kda = [i for i, k in enumerate(CFG.layer_types) if k == "kda"]
    for i, l in enumerate(kda):
        parts = jnp.concatenate([ref[n][l] for n in S.W_IN], axis=1)
        np.testing.assert_array_equal(layers["kda"]["w_in"][i], parts)
        for name in ("conv_w", "wf_up", "wg_up", "a_log", "dt_bias", "o_norm", "wo"):
            np.testing.assert_array_equal(layers["kda"][name][i], ref[name][l])
    for name in ("wq", "wk", "wv", "wg", "wo"):
        np.testing.assert_array_equal(layers["attn"][name][0], ref[name][0])
    for l in range(CFG.n_layers):
        for name in ("w_router", "router_bias", "w_gate", "w_up", "w_down",
                     "ws_gate", "ws_up", "ws_down"):
            np.testing.assert_array_equal(layers["moe"][name][l], ref[name][l])
    assert layers["kda"]["a_log"].dtype == layers["kda"]["dt_bias"].dtype == jnp.float32
    assert layers["moe"]["router_bias"].dtype == jnp.float32


@pytest.mark.parametrize("lo,held", [(0, 2), (6, 2), (4, 4)])
def test_a_share_is_served_as_the_reference_states_it(lo, held):
    """One chip's share: the router 8 wide, `held` published experts from
    `lo` held, the pairs routed elsewhere left out in the program and in the
    reference alike, and counted in the routed leaf's last column."""
    cfg = CFG.replace(n_experts_held=held, expert_lo=lo)
    ids = prompt_ids(40, 3)
    got, pool = _serve(cfg, ids, (40,), 0)
    assert_logits(got, U.ref_logits(cfg, SEED, ids))
    routed = np.asarray(pool["routed"])
    assert routed.shape == (2, 4, held + 1)
    assert (routed[0].sum(-1) == 40 * 2).all() and routed[0, :, -1].min() > 0


def test_the_shares_banks_are_slices_of_the_uncut_draw():
    whole = M.init_params(CFG, jax.random.PRNGKey(SEED))["layers"]["moe"]
    part = M.init_params(CFG.replace(n_experts_held=2, expert_lo=4),
                         jax.random.PRNGKey(SEED))["layers"]["moe"]
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(part[name], whole[name][:, 4:6])
    np.testing.assert_array_equal(part["w_router"], whole["w_router"])


def test_the_published_preset_is_the_published_model():
    """`solar-open2-250b` from the shapes alone: 48 layers 3:1 with the
    gated attention layers at 0, 4, ..., 44; 250B parameters of which a
    token computes about 15B."""
    cfg = get_model_config("solar-open2-250b")
    assert [i for i, k in enumerate(cfg.layer_types) if k == "full_attention"] \
        == list(range(0, 48, 4))
    assert (cfg.conv_channels, cfg.matrix_state_shape) == (24576, (64, 128, 128))
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert 245e9 < total < 255e9, total
    moe = shapes["layers"]["moe"]
    banks = sum(moe[n].size for n in ("w_gate", "w_up", "w_down"))
    active = total - banks + banks * 8 // 320 - shapes["embed"].size
    assert 13e9 < active < 17e9, active
    w_in = shapes["layers"]["kda"]["w_in"]
    assert w_in.shape == (36, 4096, 3 * 8192 + 2 * 128 + 64)


def test_the_kinds_table_is_what_every_recurrent_pool_is_read_from():
    """config.STATE_OF_KIND: a row a kind, no branch on a family's fields.
    The accepted families' leaves keep the shapes they had."""
    assert set(STATE_OF_KIND) == KEEPS_CONV_STATE | KEEPS_MATRIX_STATE
    for kind, (conv, state) in STATE_OF_KIND.items():
        assert (conv is not None) == (kind in KEEPS_CONV_STATE)
        assert (state is not None) == (kind in KEEPS_MATRIX_STATE)
    want = {
        "lfm2-24b-a2b": (2048, None), "minicpm-sala": (None, (32, 128, 128)),
        "granite-4.0-h-micro": (4352, (32, 128, 128)),
        "test-granite-tiny": (272, (2, 8, 128)),
        "test-sala-tiny": (None, None), "test-solar-tiny": (768, (2, 128, 128)),
        "solar-open2-250b": (24576, (64, 128, 128)),
    }
    for name, (conv, state) in want.items():
        cfg = get_model_config(name)
        if conv is not None:
            assert cfg.conv_channels == conv, name
        if state is not None:
            assert cfg.matrix_state_shape == state, name
    tiny = get_model_config("test-sala-tiny")
    assert tiny.matrix_state_shape == (tiny.linear_heads, tiny.head_dim, tiny.head_dim)
    assert get_model_config("tinyllama-1.1b").conv_channels == 2048  # (unread)


@pytest.mark.parametrize("name,slots,snaps", [
    ("test-granite-tiny", 3, 2), ("test-sala-tiny", 2, 4), ("test-lfm2-tiny", 2, 0),
    ("test-solar-tiny", 3, 2)])
def test_the_pools_leaves_are_their_kinds(name, slots, snaps):
    cfg = get_model_config(name)
    bs = cfg.sparse_block if cfg.sparse_layers else BS
    pool = P.init_pool(cfg, 6, bs, n_slots=slots, n_snapshots=snaps)
    hist = (cfg.conv_kernel - 1, cfg.conv_channels)
    if cfg.state_tails:
        assert pool["conv"].shape == (len(cfg.conv_layers), slots) + hist
        assert pool["tail"].shape == (len(cfg.conv_layers), 6) + hist
        return
    assert [a.shape for a in pool["lin"]] \
        == [(slots,) + cfg.matrix_state_shape] * len(cfg.linear_layers)
    assert [a.shape for a in pool["snap"]] \
        == [(snaps,) + cfg.matrix_state_shape] * len(cfg.linear_layers)
    if cfg.conv_layers:
        assert [a.shape for a in pool["conv"]] == [(slots,) + hist] * len(cfg.conv_layers)
        assert [a.shape for a in pool["csnap"]] == [(snaps,) + hist] * len(cfg.conv_layers)
    assert ("routed" in pool) == bool(cfg.moe_ffn_dim)


@pytest.mark.parametrize("change,what", [
    (dict(layer_types=("kda",) * 4), "at least one of each"),
    (dict(layer_types=("full_attention", "kda", "mamba", "kda")), "entries of 'kda'"),
    (dict(linear_heads=0), "needs linear_heads"),
    (dict(conv_kernel=1), "conv_kernel"),
    (dict(first_k_dense=1), "every layer routes"),
    (dict(n_experts_held=4, expert_lo=6), "not all of the router's"),
])
def test_a_configuration_the_family_cannot_be_is_refused(change, what):
    with pytest.raises(ValueError, match=what):
        CFG.replace(**change)


def test_the_other_families_take_none_of_its_fields():
    with pytest.raises(ValueError, match="delta_neg_eigval"):
        ModelConfig(delta_neg_eigval=True)
    with pytest.raises(ValueError, match="linear_heads"):
        ModelConfig(linear_heads=2)
    with pytest.raises(ValueError, match="no dense cache"):
        M.init_kv_cache(CFG, 1)
