"""What the paged attention kernels must move and compute for `kv_tokens`
KV positions (the launch record's count: per layer and K/V head, the fewest
positions the launch's rows must read) in a model whose layers are not all
attention layers (models/lfm2.py: `layer_types`): only the
`full_attention` layers own K/V, so their count multiplies, not
`num_hidden_layers` (roofline/ragged_attention.py's factor, which would
read 4.5 times too much at 2 attention layers of 9).

Bytes: a position's keys and values, 2 x num_key_value_heads x head_dim
numbers an attention layer: the published bytes (the pool stores pairs of
64-number heads side by side on 128 lanes, which adds none).
Operations: a query head's score over a position is 2 x head_dim, its share
of the value sum 2 x head_dim; all query heads, once a position (a prefill
chunk's queries each do this; the count takes one, as `kv_tokens` does).
What the packed layout spends on zero lanes (each query head is
zero-extended to the pair's 128 lanes) is not counted. Both are lower
bounds, so the share can only understate."""

from __future__ import annotations

from roofline.weights import BYTES


def attention_layers(config: dict) -> int:
    return sum(kind == "full_attention" for kind in config["layer_types"])


def head_dim(config: dict) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def kv_bytes(config: dict, kv_tokens: int) -> int:
    return (kv_tokens * attention_layers(config) * 2 * config["num_key_value_heads"]
            * head_dim(config) * BYTES[config.get("torch_dtype", "bfloat16")])


def flops(config: dict, kv_tokens: int) -> int:
    return (kv_tokens * attention_layers(config) * config["num_attention_heads"]
            * 4 * head_dim(config))


def bound(config: dict, kv_tokens: int, peaks: dict) -> tuple:
    tb = kv_bytes(config, kv_tokens) / peaks["hbm_bytes_per_s"]
    tc = flops(config, kv_tokens) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
