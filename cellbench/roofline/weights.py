"""Bytes of weights one model step must read, from the configuration's
published sizes. Every step (of any number of tokens) streams every layer's
matrices and the output head once; the input embedding table is gathered,
not streamed, and is left out. A LOWER bound on a step's memory traffic
(no KV, no activations), so the share it gives is bandwidth-bound and can
only understate how close the step is to the roofline."""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_weight_bytes(config: dict) -> int:
    D, F = config["hidden_size"], config["intermediate_size"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config.get("head_dim", D // H)
    per_layer = D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F + 2 * D
    head = D * config["vocab_size"] + D
    return (config["num_hidden_layers"] * per_layer + head) * BYTES[config.get("torch_dtype", "bfloat16")]
