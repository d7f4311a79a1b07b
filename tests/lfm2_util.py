"""Shared by the lfm2 tests: the plain reference
(cellbench/reference/conv_hybrid_moe.py) set up for a registry preset, and
one paged launch over the pool at the level of engine/paged's hooks, with
the logits of EVERY flat token handed back (the served programs unembed one
position a row)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "cellbench"))

from harness.manifest import load_module  # noqa: E402

from distributed_llm_inference_tpu.engine import paged as P  # noqa: E402
from distributed_llm_inference_tpu.models import api as M  # noqa: E402

REF = load_module("reference", "conv_hybrid_moe")


def ref_config(cfg) -> dict:
    """The reference's configuration (HF key names) of a ModelConfig."""
    return dict(
        num_hidden_layers=cfg.n_layers, num_dense_layers=cfg.first_k_dense,
        layer_types=list(cfg.layer_types), hidden_size=cfg.dim,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, conv_L_cache=cfg.conv_kernel,
        intermediate_size=cfg.ffn_dim, moe_intermediate_size=cfg.moe_ffn_dim,
        num_experts=cfg.n_experts, num_experts_per_tok=cfg.n_experts_per_tok,
        vocab_size=cfg.vocab_size, norm_eps=cfg.norm_eps,
        rope_parameters={"rope_theta": cfg.rope_theta},
        norm_topk_prob=cfg.moe_renormalize,
        routed_scaling_factor=cfg.routed_scaling,
        init={"router_bias_scale": 0.05, "router_norm_eps": cfg.router_norm_eps},
    )


_PARAMS = {}


def ref_logits(cfg, seed: int, ids, dtype=jnp.float32):
    """[len(ids), V]: the reference's logits at every position of `ids`."""
    config = ref_config(cfg)
    key = (cfg.name, seed, jnp.dtype(dtype).name)
    if key not in _PARAMS:
        _PARAMS[key] = REF.make_params(config, seed, dtype)
    params = _PARAMS[key]
    x = REF.forward(config, params, list(ids))
    return np.asarray(REF.logits(config, params, x[:len(ids)]))


def _launch_fn(cfg, params, tokens, tok_row, tok_pos, meta, pool, table):
    x = M.embed(cfg, params, tokens[:, None], tok_pos)
    x, pool = M.forward_layers(
        cfg, params["layers"], x, P._routed_reset(pool), tok_pos,
        attn_hook=P.make_ragged_fill_hook(table, meta, tok_row),
        attn_seq_len=1,
    )
    return M.unembed(cfg, params, x)[:, 0], pool


def _jit():
    # (a function of its own each time: jit's cache is keyed by the function)
    def fn(cfg, params, tokens, tok_row, tok_pos, meta, pool, table):
        return _launch_fn(cfg, params, tokens, tok_row, tok_pos, meta, pool, table)

    return jax.jit(fn, static_argnames=("cfg",), donate_argnames=("pool",))


_launch = _jit()


def launch(cfg, params, pool, table, entries, width=64, tile=8, retrace=False):
    """One ragged launch. entries: [(row, first position, token ids, kind)].
    Returns ([each entry's logits [len(ids), V]], the pool). retrace: trace
    the program anew (a test that has patched the model's code)."""
    meta, tok_row, tok_pos, offsets, _ = P.build_ragged_meta(
        [(row, start, len(ids), kind) for row, start, ids, kind in entries],
        width=width, tile=tile,
    )
    toks = np.zeros((width,), np.int32)
    for (_, _, ids, _), off in zip(entries, offsets):
        toks[off:off + len(ids)] = ids
    logits, pool = (_jit() if retrace else _launch)(
        cfg, params, jnp.asarray(toks), jnp.asarray(tok_row),
        jnp.asarray(tok_pos), jnp.asarray(meta), pool, jnp.asarray(table),
    )
    logits = np.asarray(logits)
    return [logits[off:off + len(e[2])] for e, off in zip(entries, offsets)], pool
