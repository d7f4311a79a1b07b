"""Shared by the block-diffusion tests: the plain reference
(cellbench/reference/block_diffusion_moe.py) set up for a registry preset,
and the margin of a served token under the reference's own logits at the
denoise state that reveals it."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "cellbench"))

from harness.manifest import load_module  # noqa: E402

REF = load_module("reference", "block_diffusion_moe")


def ref_config(cfg, steps: int) -> dict:
    """The reference's configuration (HF key names) of a ModelConfig."""
    return dict(
        num_hidden_layers=cfg.n_layers, hidden_size=cfg.dim,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, moe_intermediate_size=cfg.moe_ffn_dim,
        num_experts=cfg.n_experts, num_experts_per_tok=cfg.n_experts_per_tok,
        vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps, norm_topk_prob=cfg.moe_renormalize,
        diffusion=dict(block_length=cfg.diffusion_block,
                       mask_token_id=cfg.mask_token_id, denoise_steps=steps),
    )


_PARAMS = {}


def ref_params(cfg, seed: int, dtype=jnp.float32):
    key = (cfg.name, seed, jnp.dtype(dtype).name)
    if key not in _PARAMS:
        _PARAMS[key] = REF.make_params(ref_config(cfg, 1), seed, dtype)
    return _PARAMS[key]


def ref_logits(cfg, seed, steps, ids, n_prompt, dtype=jnp.float32):
    """[len(ids) - n_prompt, V]: the reference's logits for every generated
    token of `ids`, each at the denoise state that reveals it."""
    config = ref_config(cfg, steps)
    params = ref_params(cfg, seed, dtype)
    x = REF.forward(config, params, list(ids), n_prompt=n_prompt)
    return np.asarray(REF.logits(config, params, x[n_prompt - 1:len(ids) - 1]))


def margins(lg, chosen):
    """Per row: the reference's best logit minus its logit of the token
    chosen, in units of the logits' standard deviation (harness/ref_child)."""
    chosen = np.asarray(chosen)
    return (lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen]) / lg.std()
