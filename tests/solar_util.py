"""Shared by the solar_open2 (Solar-Open2) tests: the plain reference
(cellbench/reference/delta_hybrid_moe.py) set up for a registry preset. One
paged launch at the level of engine/paged's hooks is lfm2_util's `launch`."""

import jax.numpy as jnp
import numpy as np

from lfm2_util import launch  # noqa: F401  (re-exported)
from harness.manifest import load_module

REF = load_module("reference", "delta_hybrid_moe")
REF.Q_BLOCK = 16  # (the tiny sequences are a few blocks of the recurrence)


def ref_config(cfg) -> dict:
    """The reference's configuration (HF key names) of a ModelConfig."""
    return dict(
        model_type="solar_open2", num_hidden_layers=cfg.n_layers,
        gqa_layers=[i for i, k in enumerate(cfg.layer_types)
                    if k == "full_attention"],
        hidden_size=cfg.dim, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        linear_attn_config={"short_conv_kernel_size": cfg.conv_kernel,
                            "head_dim": cfg.head_dim,
                            "num_heads": cfg.linear_heads, "num_kv_heads": None},
        use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
        kda_allow_neg_eigval=cfg.delta_neg_eigval, first_k_dense_replace=0,
        moe_intermediate_size=cfg.moe_ffn_dim,
        n_routed_experts=cfg.experts_held,
        n_shared_experts=cfg.n_shared_experts,
        expert_share={"router_width": cfg.n_experts, "expert_lo": cfg.expert_lo},
        num_experts_per_tok=cfg.n_experts_per_tok, vocab_size=cfg.vocab_size,
        rms_norm_eps=cfg.norm_eps, norm_topk_prob=cfg.moe_renormalize,
        routed_scaling_factor=cfg.routed_scaling,
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
