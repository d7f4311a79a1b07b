"""Arch dispatch: one functional interface over the nine model families.

The engine and pipeline runtime call these; cfg.arch picks the family.

  arch            module             layers    cache                            meshes
  llama           llama.py           scanned   K/V, dense or the paged pool     dp, pp, sp, tp, ep
  gpt2            gpt2.py            scanned   K/V, dense or the paged pool     dp, pp, tp
  mla_moe         mla_moe.py         scanned   a latent a token                 one device
  lfm2            lfm2.py            unrolled  K/V + a conv state a slot        one device
  afmoe           afmoe.py           unrolled  K/V grouped by layer kind        one device
  mimo_v2         mimo_v2.py         unrolled  K/V grouped, rows a kind's own   one device
  minicpm_sala    minicpm_sala.py    unrolled  K/V + compressed keys + a        one device, the
                                               matrix state a slot              paged pool only
  granite_hybrid  granite_hybrid.py  unrolled  K/V + conv and matrix states     (the same)
  solar_open2     solar_open2.py     unrolled  granite_hybrid's, by layer kind  (the same)

(llama also: an every-expert MoE FFN, routed experts at cfg.moe_ffn_dim > 0,
block diffusion at cfg.diffusion_block > 0. A configuration that routes
serves one device from the paged pool: engine/paged.refuse_unsupported_latent.
Routed experts are one module, models/experts.py; what the six unrolled
families share, their one layer loop included, is models/stack.py.)

The contract a family module meets, which is all this file calls:
  init_params(cfg, key)                      the seeded tree {"embed", ...,
                                             "layers": {...}}
  init_kv_cache(cfg, batch, max_seq, n_layers)  the dense cache, or a refusal
  embed(cfg, params, tokens, pos) -> x       float32 for the unrolled families
  forward_layers(cfg, layers, x, cache, pos, update_gate, tp_axis,
                 attn_hook, valid_start, ep_axis, attn_seq_len) -> (x, cache)
  unembed(cfg, params, x) -> float32 logits
  forward(cfg, params, tokens, cache, pos)   the three over a dense cache
What a family cannot take of these (a mesh axis, left-padded rows, a dense
cache) it refuses with a ValueError at the call.
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
    # All nine families expose the same seams: attn_hook (the shared
    # attention/cache strategy hook — parallel/context.py, the paged
    # pool) and attn_seq_len (paged logical window). valid_start (ragged
    # left-padding) is llama's and mla_moe's; ep_axis (the every-expert
    # MoE FFN's mesh axis) and lora_pages (paged adapter delta) are
    # llama-only — gpt2's forward_layers rejects all three loudly
    # (learned absolute positions are not shift-invariant; no MoE
    # blocks; no lora leaves), the routed-expert paths of llama and
    # mla_moe reject ep_axis (models/experts.routed_ffn's expert_lo is
    # the share a mesh would hold; no ep axis sums the parts yet), and
    # the six unrolled families reject update_gate, tp_axis, ep_axis and
    # valid_start in one place (models/stack.forward_layers).
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
