"""The latent-attention, routed-expert family (models/mla_moe.py, the
latent pool of engine/paged.py, the latent form of the paged kernels) held
to the plain reference the benchmark ships (cellbench/reference/mla_moe.py)
at a size the CPU runs: float32, seeded weights, 1e-4 relative.

And the other side of the same change: a per-head K/V model still builds
the pool, the programs and the outputs it built before (the last section).
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.config import MeshConfig, resolve_attn_impl
from distributed_llm_inference_tpu.engine import paged as P
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models import mla_moe as MM
from distributed_llm_inference_tpu.runtime import create_backend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "cellbench")
sys.path.insert(0, BENCH)

from harness.manifest import load_module  # noqa: E402

SEED, BS, TILE = 11, 16, 8
SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared", "mla_absorb")


@pytest.fixture(scope="module")
def tiny():
    """(cfg, params, the benchmark's tiny configuration file, reference
    module, reference params): both sides make their weights from SEED."""
    cfg = get_model_config("test-mla-moe-tiny", dtype="float32", eos_token_id=-1)
    with open(os.path.join(BENCH, "tests", "data", "configs", "tiny-mla-moe.json")) as f:
        config = json.load(f)
    ref = load_module("reference", "mla_moe")
    return (cfg, M.init_params(cfg, jax.random.PRNGKey(SEED)), config, ref,
            ref.make_params(config, SEED, jnp.float32))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 250, size=n).astype(np.int32)


def _ref_logits(tiny, ids):
    _, _, config, ref, rp = tiny
    x = ref.forward(config, rp, [int(i) for i in ids])
    return np.asarray(ref.logits(config, rp, x[: len(ids)]))


def _close(got, want, tol=1e-4):
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() <= tol * scale, (
        np.abs(np.asarray(got) - want).max() / scale
    )


# -- the reference and the program make the same weights ----------------------

def test_both_sides_draw_the_same_weights_from_the_seed(tiny):
    _, params, _, _, rp = tiny
    Ld = 1
    for stack, lo in (("dense", 0), ("moe", Ld)):
        for name, leaf in params["layers"][stack].items():
            for i in range(leaf.shape[0]):
                np.testing.assert_array_equal(leaf[i], rp[name][lo + i], err_msg=name)
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(params[name], rp[name])


# -- whole forward -------------------------------------------------------------

def test_whole_forward_agrees_with_the_reference(tiny):
    cfg, params = tiny[:2]
    ids = _ids(70)
    cache = M.init_kv_cache(cfg, 1, 128)
    logits, _ = M.forward(cfg, params, jnp.asarray(ids)[None], cache, jnp.int32(0))
    _close(logits[0], _ref_logits(tiny, ids))


def test_absorbed_attention_is_plain_attention(tiny):
    """One layer's attention sublayer, absorbed over the latent cache,
    against keys and values formed for every head (the reference's)."""
    cfg, params, config, ref, rp = tiny
    T = 48
    x = jax.random.normal(jax.random.PRNGKey(3), (T, cfg.dim), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"]["moe"].items()}
    cache = jnp.zeros((1, 1, 64, cfg.latent_row), jnp.float32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    from distributed_llm_inference_tpu.ops.attention import causal_mask
    got, _ = MM.attention(cfg, lp, x[None], cache, jnp.int32(0), positions,
                          causal_mask(jnp.int32(0), T, 64), None,
                          MM.latent_attn_hook)
    s = ref.sizes(config)
    want = ref.attention(
        jnp.pad(x, ((0, ref.Q_BLOCK - T), (0, 0))),
        {name: rp[name][1] for name in ("attn_norm", "wq", "w_kva", "kv_norm",
                                        "w_kvb", "wo")},
        H=s["H"], r=s["r"], dn=s["dn"], dr=s["dr"], dv=s["dv"],
        theta=s["theta"], eps=s["eps"])
    _close(got[0], np.asarray(want[:T]), 2e-5)


# -- prefill in chunks + decode through the latent pool, then a prefix hit ----

@functools.partial(jax.jit, static_argnames=("cfg",))
def _ragged_forward(cfg, params, pool, table, flat, tok_pos, meta, tok_row):
    """One program for every ragged launch of a test (eagerly, each launch
    compiled the layer scan anew)."""
    x = M.embed(cfg, params, flat[:, None], tok_pos)
    x, pool = M.forward_layers(
        cfg, params["layers"], x, pool, tok_pos,
        attn_hook=P.make_ragged_fill_hook(table, meta, tok_row), attn_seq_len=1)
    return M.unembed(cfg, params, x)[:, 0], pool


_decode_step = jax.jit(P._forward_step_paged, static_argnums=0)


def _launch(cfg, params, pool, table, entries, toks, width):
    meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
        entries, width=width, tile=TILE)
    flat = np.zeros((width,), np.int32)
    for (_, _, n, _), off, t in zip(entries, offsets, toks):
        flat[off:off + n] = t
    lg, pool = _ragged_forward(
        cfg, params, pool, table, jnp.asarray(flat), jnp.asarray(tok_pos),
        jnp.asarray(meta), jnp.asarray(tok_row))
    return lg, pool, offsets


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_decode_and_prefix_hit_agree_with_the_reference(tiny, impl):
    cfg = resolve_attn_impl(tiny[0], impl)
    params = tiny[1]
    ids, tail = _ids(52, 1), _ids(9, 2)
    want = _ref_logits(tiny, ids)
    pool = P.init_pool(cfg, 24, BS)
    MB = 6
    table = np.zeros((2, MB), np.int32)
    table[0, :5] = [3, 7, 2, 9, 5]
    got = np.zeros((40, cfg.vocab_size), np.float32)
    # prefill: 40 prompt tokens in chunks of 24 and 16 (mixed-step launches)
    for start, n in ((0, 24), (24, 16)):
        lg, pool, offs = _launch(cfg, params, pool, jnp.asarray(table),
                                 [(0, start, n, P.RAGGED_PREFILL)],
                                 [ids[start:start + n]], 32)
        got[start:start + n] = lg[offs[0]:offs[0] + n]
    _close(got, want[:40])
    # decode: teacher-forced single steps through the decode hook
    for p in range(40, 52):
        lg, pool = _decode_step(
            cfg, params, jnp.asarray(ids[p:p + 1])[:, None], pool,
            jnp.asarray(table[:1]), jnp.asarray([p], jnp.int32))
        _close(lg[0], want[p])
    # routed counts rode along: every live token, top-k experts, each layer
    routed = np.asarray(pool["routed"])
    assert routed[0].sum() == (40 + 12) * cfg.n_experts_per_tok * 2
    # a second request shares the first's three full blocks (48 tokens) and
    # prefills only its own tail, beside a decode row of the first
    table[1, :5] = [3, 7, 2, 11, 12]
    seq = np.concatenate([ids[:48], tail])
    want2 = _ref_logits(tiny, seq)
    lg, pool, offs = _launch(
        cfg, params, pool, jnp.asarray(table),
        [(0, 52, 1, P.RAGGED_DECODE), (1, 48, 9, P.RAGGED_PREFILL)],
        [ids[:1], tail], 32)
    _close(lg[offs[1]:offs[1] + 9], want2[48:])


def test_pallas_and_xla_read_the_latent_pool_alike(tiny):
    cfg, params = tiny[:2]
    ids = _ids(37, 4)
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32)[None])
    out = {}
    for impl in ("xla", "pallas"):
        c = resolve_attn_impl(cfg, impl)
        lg, pool, _ = _launch(c, params, P.init_pool(c, 8, BS), table,
                              [(0, 0, 37, P.RAGGED_PREFILL)], [ids], 40)
        step, _ = P._forward_step_paged(
            c, params, jnp.asarray([[5]]), pool, table, jnp.asarray([37]),
            active=jnp.asarray([True]))
        out[impl] = np.concatenate([np.asarray(lg[:37]), np.asarray(step)])
    np.testing.assert_allclose(out["pallas"], out["xla"], rtol=0, atol=2e-5)


def test_a_freed_slot_and_launch_padding_reach_no_expert(tiny):
    cfg = resolve_attn_impl(tiny[0], "pallas")
    params = tiny[1]
    pool = P.init_pool(cfg, 8, BS)
    table = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    _, pool = P._forward_step_paged(
        cfg, params, jnp.asarray([[5], [6]]), pool, table,
        jnp.asarray([3, 4]), active=jnp.asarray([True, False]))
    routed = np.asarray(pool["routed"])
    assert routed[0].sum() == 1 * cfg.n_experts_per_tok * 2
    assert routed[1].sum() == cfg.n_experts_per_tok * 2  # distinct experts a token


# -- the routed layer -----------------------------------------------------------

def _moe_inputs(tiny, n=29):
    cfg, params = tiny[:2]
    moe = params["layers"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(9), (n, cfg.dim), jnp.float32)
    return cfg, moe, {k: moe[k] for k in MM.BANKS}, h


def _dense_mask_form(cfg, moe, layer, h, chosen, weights):
    """Every expert on every token, weighed by a mask: what the routed
    layer must equal."""
    w = jnp.sum(jax.nn.one_hot(chosen, cfg.n_experts) * weights[..., None], axis=1)
    ys = jax.vmap(lambda g, u, d: MM.swiglu(h, g, u, d))(
        moe["w_gate"][layer], moe["w_up"][layer], moe["w_down"][layer])
    return jnp.einsum("end,ne->nd", ys, w)


@pytest.mark.parametrize("layer", [0, 1])
def test_routed_layer_is_the_dense_mask_form(tiny, layer):
    cfg, moe, banks, h = _moe_inputs(tiny)
    chosen, weights = MM.route(cfg, h, moe["w_router"][layer], moe["router_bias"][layer])
    out, sizes = MM.routed_ffn(cfg, banks, jnp.int32(layer), h, chosen, weights)
    _close(out, np.asarray(_dense_mask_form(cfg, moe, layer, h, chosen, weights)), 1e-5)
    assert int(sizes.sum()) == h.shape[0] * cfg.n_experts_per_tok
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(chosen).ravel(), minlength=cfg.n_experts))


def test_selection_bias_changes_choices_and_not_weights(tiny):
    cfg, moe, _, h = _moe_inputs(tiny, 256)
    wr, b = moe["w_router"][0], moe["router_bias"][0]
    chosen_b, w_b = MM.route(cfg, h, wr, b)
    chosen_0, w_0 = MM.route(cfg, h, wr, jnp.zeros_like(b))
    same = np.all(np.sort(chosen_b, -1) == np.sort(chosen_0, -1), axis=-1)
    assert 0 < same.sum() < len(same)  # the bias moved some choices, not all
    # the weights are the chosen experts' scores alone, renormalized and scaled
    s = jax.nn.sigmoid(h @ wr)
    picked = jnp.take_along_axis(s, chosen_b, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling
    np.testing.assert_allclose(w_b, want, rtol=1e-5)
    np.testing.assert_allclose(w_b.sum(-1), cfg.routed_scaling, rtol=1e-5)
    # where the bias changed nothing, nothing changed
    order_b, order_0 = np.argsort(chosen_b, -1), np.argsort(chosen_0, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w_b), order_b, -1)[same],
        np.take_along_axis(np.asarray(w_0), order_0, -1)[same], rtol=1e-6)


def test_eight_shares_of_two_experts_add_up_to_the_whole_layer(tiny):
    """The model-configs guide's share test: a chip that holds 2 of the 16
    experts routes over all 16 and computes its own experts' part; the
    eight parts and the shared expert, counted once, are the whole layer,
    which is the reference's."""
    cfg, moe, banks, h = _moe_inputs(tiny)
    layer = 1
    lp = {k: v[layer] for k, v in moe.items() if k not in MM.BANKS}
    chosen, weights = MM.route(cfg, h, lp["w_router"], lp["router_bias"])
    parts, got = [], 0
    for share in range(8):
        held = {k: v[:, 2 * share:2 * share + 2] for k, v in banks.items()}
        out, sizes = MM.routed_ffn(cfg, held, jnp.int32(layer), h, chosen, weights,
                                   expert_lo=2 * share)
        parts.append(out)
        got += int(sizes.sum())
    assert got == h.shape[0] * cfg.n_experts_per_tok  # every pair, once
    shared = MM.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    whole, _ = MM.moe_ffn(cfg, lp, banks, jnp.int32(layer), h[None])
    _close(sum(parts) + shared, np.asarray(whole[0]), 1e-5)
    _, _, config, ref, rp = tiny
    s = ref.sizes(config)
    w = ref.expert_weights(h, rp["w_router"][2], rp["router_bias"][2], k=s["k"],
                           renorm=s["renorm"], scaling=s["scaling"])
    routed = sum(ref._swiglu(h, rp["w_gate"][2][e], rp["w_up"][2][e], rp["w_down"][2][e])
                 * w[:, e:e + 1] for e in range(s["E"]))
    want = routed + ref._swiglu(h, rp["ws_gate"][2], rp["ws_up"][2], rp["ws_down"][2])
    _close(whole[0], np.asarray(want), 1e-5)


# -- the engine: served, counted, refused --------------------------------------

def _cont(cfg, params, **kw):
    eng = InferenceEngine(cfg, params=params, engine_cfg=EngineConfig(
        prefix_cache_entries=8, chunked_prefill=True, step_token_budget=32,
        kv_shadow=False))
    return ContinuousEngine(eng, n_slots=3, chunk_steps=4, chunk_lag=2,
                            slot_max_seq=128, kv_pool_blocks=40,
                            kv_block_size=BS, restart_backoff_s=0.01, **kw)


def test_the_fleet_serves_it_and_the_launch_record_counts_what_it_routed(tiny):
    cfg = resolve_attn_impl(tiny[0], "pallas")
    cont = _cont(cfg, tiny[1])
    try:
        prompt = "a document of some length, asked about twice over " * 2
        a = cont.submit(prompt + "?", max_tokens=9, greedy=True, chat=False)
        b = cont.submit(prompt + "!", max_tokens=9, greedy=True, chat=False)
    finally:
        cont.close()
        cont._thread.join(timeout=30)
    assert a["status"] == b["status"] == "success", (a, b)
    assert b["prefix_cached_tokens"] >= 4 * BS  # a hit over latent blocks
    snap = cont.engine.metrics.snapshot()

    def total(name):
        return sum(s["value"] for s in snap[name]["series"])

    Lm = cfg.n_layers - cfg.first_k_dense
    tokens = a["prompt_tokens"] + b["prompt_tokens"] - b["prefix_cached_tokens"]
    pairs = total("dli_moe_expert_tokens_total")
    # every prefilled token and every decode row-step, top-k experts a layer;
    # a row's last launched steps may outrun its budget (they count too)
    least = (tokens + 2 * 8) * cfg.n_experts_per_tok * Lm
    assert least <= pairs <= least + 2 * 8 * cfg.n_experts_per_tok * Lm
    touched, slots = (total("dli_moe_experts_touched_total"),
                      total("dli_moe_expert_slots_total"))
    assert 0 < touched <= slots and touched <= pairs
    plans = [e for e in cont.engine.flight.events() if e["kind"] == "plan"]
    assert plans and all("kv_tokens" in e for e in plans)


def test_start_up_refuses_what_a_latent_pool_does_not_carry(tiny):
    cfg, params = tiny[:2]
    for kw, word in ((dict(quant="int8"), "quantization"),
                     (dict(kv_quant="int8"), "int8 pool"),
                     (dict(mesh_cfg=MeshConfig(tp=2)), "meshes")):
        with pytest.raises(ValueError, match=word):
            create_backend(cfg, params=params, **kw)
    with pytest.raises(ValueError, match="shadow"):
        _cont(cfg, params, kv_shadow=True)
    eng = InferenceEngine(cfg, params=params, engine_cfg=EngineConfig())
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(eng, n_slots=2)
    P.refuse_unsupported_latent(get_model_config("test-llama-tiny"),
                                quant="int8", kv_shadow=True)  # per-head: passes


# -- the dense path is untouched ------------------------------------------------

def _step_programs(name):
    """`dense_equal.programs` of a tiny preset's fleet (3 slots over 12
    blocks of BS, float32, the registry's depth), compiled for the CPU, and
    the shape of the mixed step's one fetch."""
    import dense_equal

    built = dense_equal.programs(name, 3, 12, 4 * BS, block_size=BS, tile=TILE,
                                 layers=0, described=False, dtype="float32")
    return built, built.compiled["mixed_step_ragged"].out_info[0]


def _names(text):
    """(module name, the name stacks its instructions were traced under:
    scopes and inner jits included)."""
    import re

    # the instructions' own name stacks only: the text also ends in a table
    # of every frame the PROCESS has traced, whatever program it was for
    stacks = "\n".join(sorted(set(re.findall(r'op_name="([^"]*)"', text))))
    return text.split("HloModule ", 1)[1].split(",", 1)[0], stacks


@pytest.mark.parametrize("name, kv", [
    ("test-llama-tiny", 2), ("test-olmo2-tiny", 4), ("test-moe-tiny", 2)])
def test_a_dense_configuration_keeps_its_pool_its_programs_and_their_names(name, kv):
    """Pinned for later PRs (ISSUE 28): a per-head K/V model builds the
    [L, n_blocks, KV, bs, Dh] K and V pool and nothing else, its two step
    programs keep the module names a device trace is read by, the mixed
    step's one fetch is its five rows, and none of the scopes or outputs
    the latent family added appears in them."""
    built, packed = _step_programs(name)
    cfg, pool = built.cfg, built.pool
    assert set(pool) == {"k", "v"}
    for leaf in pool.values():
        assert leaf.shape == (cfg.n_layers, 12, kv, BS, cfg.head_dim)
    assert packed.shape == (5, 3)
    for program, text in built.texts.items():
        got, text = _names(text)
        assert got == f"jit_{program}"
        for word in SCOPES + ("routed_expert_matmul", "routed"):
            assert word not in text, (program, word)


def test_the_latent_family_carries_the_names_the_benchmark_reads(tiny):
    built, packed = _step_programs(tiny[0].name)
    cfg, pool = built.cfg, built.pool
    Lm = cfg.n_layers - cfg.first_k_dense
    assert set(pool) == {"dense", "moe", "routed"}
    assert pool["moe"].shape == (Lm, 12, 1, BS, cfg.latent_row)
    assert pool["routed"].shape == (2, Lm, cfg.n_experts)
    assert cfg.latent_row % 128 == 0 and cfg.latent_row >= cfg.latent_dim
    assert packed.shape == (5 + -(-2 * Lm * cfg.n_experts // 3), 3)
    for program, text in built.texts.items():
        got, text = _names(text)
        assert got == f"jit_{program}"
        for scope in SCOPES:
            assert scope in text, (program, scope)
        assert "routed_expert_matmul" in text
