"""What the paged attention kernels' latent form (ops/paged_attention.py
`_latent_walk`: one row [c | k_r] a token and layer, read absorbed) must
move and compute for `kv_tokens` KV positions (the launch record's count:
per layer, the fewest positions the launch's rows must read).

Bytes: kv_lora_rank + qk_rope_head_dim numbers a position and layer, the
576 that carry data: the pool stores 640 (whole 128-lane tiles) and the
kernel's DMA reads the pad too, so this is the lower of the two counts.
Operations, absorbed form: a query head's score over a row is 2 x 576, its
share of the value sum 2 x 512; all heads, once a position (a prefill
chunk's queries each do this; the count takes one, as `kv_tokens` does).
Both are lower bounds, so the share can only understate."""

from __future__ import annotations

from roofline.weights import BYTES


def row_numbers(config: dict) -> int:
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def kv_bytes(config: dict, kv_tokens: int) -> int:
    return (kv_tokens * config["num_hidden_layers"] * row_numbers(config)
            * BYTES[config.get("torch_dtype", "bfloat16")])


def flops(config: dict, kv_tokens: int) -> int:
    per_head = 2 * row_numbers(config) + 2 * config["kv_lora_rank"]
    return (kv_tokens * config["num_hidden_layers"]
            * config["num_attention_heads"] * per_head)


def bound(config: dict, kv_tokens: int, peaks: dict) -> tuple:
    tb = kv_bytes(config, kv_tokens) / peaks["hbm_bytes_per_s"]
    tc = flops(config, kv_tokens) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
