"""Shared by the mimo_v2 (MiMo-V2.5) tests: the plain reference
(cellbench/reference/window_sink_moe.py) set up for a registry preset. One
paged launch at the level of engine/paged's hooks is lfm2_util's `launch`
(the launch table of a grouped pool is its groups' side by side)."""

import jax.numpy as jnp
import numpy as np

from lfm2_util import launch  # noqa: F401  (re-exported)
from harness.manifest import load_module

REF = load_module("reference", "window_sink_moe")


def ref_config(cfg) -> dict:
    """The reference's configuration (HF key names) of a ModelConfig."""
    kinds = {"full_attention": 0, "sliding_attention": 1}
    return dict(
        num_hidden_layers=cfg.n_layers,
        hybrid_layer_pattern=[kinds[k] for k in cfg.layer_types],
        moe_layer_freq=[int(i >= cfg.first_k_dense) for i in range(cfg.n_layers)],
        hidden_size=cfg.dim, num_attention_heads=cfg.n_heads,
        swa_num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        swa_num_key_value_heads=cfg.group_kv_heads("window"),
        head_dim=cfg.head_dim, swa_head_dim=cfg.head_dim,
        v_head_dim=cfg.value_dim, swa_v_head_dim=cfg.value_dim,
        partial_rotary_factor=(cfg.rotary_dim + 0.1) / cfg.head_dim,
        rope_theta=cfg.rope_theta, swa_rope_theta=cfg.rope_local_theta,
        attention_value_scale=cfg.attn_value_scale,
        add_full_attention_sink_bias=False,
        add_swa_attention_sink_bias=cfg.window_sink,
        sliding_window=cfg.attn_window, intermediate_size=cfg.ffn_dim,
        moe_intermediate_size=cfg.moe_ffn_dim,
        n_routed_experts=cfg.experts_held,
        expert_share={"router_width": cfg.n_experts, "expert_lo": cfg.expert_lo},
        num_experts_per_tok=cfg.n_experts_per_tok, vocab_size=cfg.vocab_size,
        layernorm_epsilon=cfg.norm_eps, norm_topk_prob=cfg.moe_renormalize,
        routed_scaling_factor=None,
        init={"router_bias_scale": 0.05, "sink_scale": 0.5,
              "router_norm_eps": cfg.router_norm_eps},
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
