"""What the paged attention kernels must move and compute in a model whose
layers are of two kinds (models/afmoe.py: `layer_types`), from the launch
record's counts per kind: `kv_tokens_global` / `kv_tokens_window`, the
fewest KV positions per layer OF THE KIND and K/V head the launch's rows
must read, the window one clipped at `sliding_window`. A `full_attention`
layer reads a row's whole context and a `sliding_attention` layer its last
window, so each count multiplies its own kind's layers
(roofline/ragged_attention.py's one count x `num_hidden_layers` would read
several times too much here).

Bytes: a position's keys and values, 2 x num_key_value_heads x head_dim
numbers a layer. Operations: a query head's score over a position is 2 x
head_dim, its share of the value sum 2 x head_dim; all query heads, once a
position (a prefill chunk's queries each do this; the count takes one, as
the record does). Both are lower bounds, so the share can only understate."""

from __future__ import annotations

from roofline.weights import BYTES


def layers(config: dict) -> dict:
    kinds = list(config["layer_types"])
    return {"global": kinds.count("full_attention"),
            "window": kinds.count("sliding_attention")}


def positions(config: dict, launch: dict) -> int:
    """KV positions x layers a launch must read (per K/V head)."""
    n = layers(config)
    return (int(launch["kv_tokens_global"]) * n["global"]
            + int(launch["kv_tokens_window"]) * n["window"])


def kv_bytes(config: dict, positions: int) -> int:
    return (positions * 2 * config["num_key_value_heads"] * config["head_dim"]
            * BYTES[config.get("torch_dtype", "bfloat16")])


def flops(config: dict, positions: int) -> int:
    return positions * config["num_attention_heads"] * 4 * config["head_dim"]


def bound(config: dict, positions: int, peaks: dict) -> tuple:
    tb = kv_bytes(config, positions) / peaks["hbm_bytes_per_s"]
    tc = flops(config, positions) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
