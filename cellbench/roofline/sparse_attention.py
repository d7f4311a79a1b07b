"""What the paged attention kernels must move and compute for `kv_tokens`
SELECTED KV positions (the launch record's count in a fleet with sparse
attention layers: per layer and K/V head, what the launch's rows read after
the selection) in a model whose layers are not all attention layers
(models/minicpm_sala.py: `mixer_types`): only the `minicpm4` layers own K/V.

Bytes: a position's keys and values, 2 x num_key_value_heads x head_dim
numbers a sparse layer. Operations: a query head's score over a position is
2 x head_dim, its share of the value sum 2 x head_dim; all query heads, once
a position. The selection's own work (scores against the compressed keys,
top-k) is not the kernels' and is not counted; nor is what a tile of several
queries walks beyond its last query's choice. Both are lower bounds, so the
share can only understate."""

from __future__ import annotations

from roofline.weights import BYTES


def sparse_layers(config: dict) -> int:
    return sum(kind == "minicpm4" for kind in config["mixer_types"])


def kv_bytes(config: dict, kv_tokens: int) -> int:
    return (kv_tokens * sparse_layers(config) * 2 * config["num_key_value_heads"]
            * config["head_dim"] * BYTES[config.get("torch_dtype", "bfloat16")])


def flops(config: dict, kv_tokens: int) -> int:
    return (kv_tokens * sparse_layers(config) * config["num_attention_heads"]
            * 4 * config["head_dim"])


def bound(config: dict, kv_tokens: int, peaks: dict) -> tuple:
    tb = kv_bytes(config, kv_tokens) / peaks["hbm_bytes_per_s"]
    tc = flops(config, kv_tokens) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
