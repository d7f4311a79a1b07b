"""Arch dispatch: one functional interface over the four model families.

The engine and pipeline runtime call these; cfg.arch picks the family
(llama: RMSNorm/RoPE/GQA/SwiGLU, with an every-expert MoE FFN (Mixtral,
qwen3-30b-a3b) or, at cfg.moe_ffn_dim > 0, routed experts, and at
cfg.diffusion_block > 0 the block-diffusion mask (SDAR) — gpt2:
LayerNorm/learned-pos/MHA/gelu — mla_moe: latent attention, routed
experts, a leading dense stack). llama and gpt2 share the stacked-layer
pytree + KV-cache layout, so the pipeline partitioner and cache plumbing
are agnostic between them; mla_moe has two stacks and a latent cache and
serves one device only; lfm2 (models/lfm2.py) alternates gated short
convolutions with attention in one unscanned stack, keeps K/V for its
attention layers alone and a recurrent state a row beside them, and serves
one device from the paged pool only; afmoe (models/afmoe.py) mixes
sliding-window and global gated attention layers in one unscanned stack,
holds one chip's share of the routed experts where the configuration says
so, and serves one device from a pool grouped by layer kind; minicpm_sala
(models/minicpm_sala.py) alternates sparse attention layers, which read the
blocks a score over mean-pooled keys selects, with decayed linear-attention
layers, keeps K/V and compressed keys for the first and a float32 matrix
state a row for the second, and serves one device from the paged pool only;
granite_hybrid (models/granite_hybrid.py) alternates Mamba-2 state-space
layers, which keep a convolution state AND a float32 matrix state a row,
with attention layers that take no position encoding, and serves one device
from the paged pool only; mimo_v2 (models/mimo_v2.py) mixes sliding-window
layers that have a learned sink in their softmax with global layers, the two
kinds with their own K/V head counts and rotation bases and keys wider than
values, over routed experts with no shared one, holds one chip's share of
them where the configuration says so, and serves one device from a pool
grouped by layer kind whose groups' rows are their own kinds'; solar_open2
(models/solar_open2.py) alternates gated delta-rule layers (Kimi Delta
Attention: a convolution on each of q, k and v, a float32 matrix state with a
decay a key channel and beta up to 2, ops/delta_rule.py), which keep the
convolutions' state AND the matrix state a row, with gated attention layers
that take no position encoding, every layer over routed experts beside a
shared one, holds one chip's share of them where the configuration says so,
and serves one device from the paged pool only, with the state leaves and
the snapshot pool granite_hybrid's by what the layer kind keeps.
Routed experts
are one module for the families that have them (models/experts.py: `route`, `routed_ffn`, the
grouped product): a configuration that routes serves one device, from the
paged pool (engine/paged.refuse_unsupported_latent).
"""

from __future__ import annotations

from ..config import ModelConfig
from . import (afmoe, gpt2, granite_hybrid, lfm2, llama, mimo_v2,
               minicpm_sala, mla_moe, solar_open2)

_FAMILIES = {"llama": llama, "gpt2": gpt2, "mla_moe": mla_moe, "lfm2": lfm2,
             "afmoe": afmoe, "minicpm_sala": minicpm_sala,
             "granite_hybrid": granite_hybrid, "mimo_v2": mimo_v2,
             "solar_open2": solar_open2}
FAMILIES = tuple(_FAMILIES)  # the arch names, for the engines' start-up checks


def family(cfg: ModelConfig):
    return _FAMILIES[cfg.arch]


def init_params(cfg, key):
    return family(cfg).init_params(cfg, key)


def init_kv_cache(cfg, batch, max_seq=None, n_layers=None):
    return family(cfg).init_kv_cache(cfg, batch, max_seq=max_seq, n_layers=n_layers)


def embed(cfg, params, tokens, pos=0):
    return family(cfg).embed(cfg, params, tokens, pos)


def forward_layers(cfg, layers, x, cache, pos, update_gate=None, tp_axis=None,
                   attn_hook=None, valid_start=None, ep_axis=None,
                   attn_seq_len=None, lora_pages=None):
    # All three families expose the same seams: attn_hook (the shared
    # attention/cache strategy hook — parallel/context.py, the paged
    # pool) and attn_seq_len (paged logical window). valid_start (ragged
    # left-padding) is llama's and mla_moe's; ep_axis (the every-expert
    # MoE FFN's mesh axis) and lora_pages (paged adapter delta) are
    # llama-only — gpt2's forward_layers rejects all three loudly
    # (learned absolute positions are not shift-invariant; no MoE
    # blocks; no lora leaves), and the routed-expert paths of llama and
    # mla_moe reject ep_axis (models/experts.routed_ffn's expert_lo is
    # the share a mesh would hold; no ep axis sums the parts yet).
    if lora_pages is not None and cfg.arch != "llama":
        raise ValueError(
            f"lora_pages (runtime adapters) requires the llama family; "
            f"got {cfg.arch!r}"
        )
    if (attn_hook is not None or valid_start is not None
            or ep_axis is not None or attn_seq_len is not None
            or lora_pages is not None):
        # gpt2.forward_layers has no lora_pages parameter; only thread
        # it when set (guaranteed llama by the check above)
        extra = {} if lora_pages is None else {"lora_pages": lora_pages}
        return family(cfg).forward_layers(
            cfg, layers, x, cache, pos, update_gate, tp_axis, attn_hook,
            valid_start, ep_axis, attn_seq_len=attn_seq_len, **extra,
        )
    return family(cfg).forward_layers(cfg, layers, x, cache, pos, update_gate,
                                      tp_axis)


def unembed(cfg, params, x):
    return family(cfg).unembed(cfg, params, x)


def forward(cfg, params, tokens, cache, pos):
    return family(cfg).forward(cfg, params, tokens, cache, pos)
