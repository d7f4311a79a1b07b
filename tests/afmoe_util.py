"""Shared by the afmoe (Trinity) tests: the plain reference
(cellbench/reference/windowed_gated_moe.py) set up for a registry preset.
One paged launch at the level of engine/paged's hooks is lfm2_util's
`launch` (the launch table of a grouped pool is its groups' side by side)."""

import jax.numpy as jnp
import numpy as np

from lfm2_util import launch  # noqa: F401  (re-exported)
from harness.manifest import load_module

REF = load_module("reference", "windowed_gated_moe")


def ref_config(cfg) -> dict:
    """The reference's configuration (HF key names) of a ModelConfig."""
    return dict(
        num_hidden_layers=cfg.n_layers, num_dense_layers=cfg.first_k_dense,
        layer_types=list(cfg.layer_types), hidden_size=cfg.dim,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_size=cfg.ffn_dim,
        moe_intermediate_size=cfg.moe_ffn_dim, num_experts=cfg.experts_held,
        num_shared_experts=cfg.n_shared_experts,
        expert_share={"router_width": cfg.n_experts, "expert_lo": cfg.expert_lo},
        num_experts_per_tok=cfg.n_experts_per_tok, vocab_size=cfg.vocab_size,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.attn_window, route_norm=cfg.moe_renormalize,
        route_scale=cfg.routed_scaling, mup_enabled=cfg.embed_scale,
        init={"router_bias_scale": 0.05, "router_norm_eps": cfg.router_norm_eps},
    )


_PARAMS = {}


def ref_params(cfg, seed: int, dtype=jnp.float32):
    key = (cfg, seed, jnp.dtype(dtype).name)
    if key not in _PARAMS:
        _PARAMS[key] = REF.make_params(ref_config(cfg), seed, dtype)
    return _PARAMS[key]


def ref_logits(cfg, seed: int, ids, dtype=jnp.float32):
    """[len(ids), V]: the reference's logits at every position of `ids`."""
    config, params = ref_config(cfg), ref_params(cfg, seed, dtype)
    x = REF.forward(config, params, list(ids))
    return np.asarray(REF.logits(config, params, x[:len(ids)]))
