"""What the ragged paged attention kernel (`ops/paged_attention.py`
`_ragged_kernel`) must move and compute in one step, from shapes alone.

For a row of context length n (window-clipped), a layer reads n keys and n
values of KV heads x head_dim each, once per query tile of the row; a decode
row is one tile. Operations: 4 x n x H x Dh per query token per layer (QK^T
and PV). At decode shapes (one query token per row) the bytes bound the
time by far: 4*n*H*Dh flops against 4*n*KV*Dh bytes is H/KV flops per byte,
under 197e12/819e9 = 240 for every model here, so the share is reported
against the bandwidth bound."""

from __future__ import annotations

from roofline.weights import BYTES


def _dims(config: dict):
    D, H = config["hidden_size"], config["num_attention_heads"]
    return (config["num_hidden_layers"], H, config["num_key_value_heads"],
            config.get("head_dim", D // H), config.get("sliding_window"))


def kv_bytes_per_step(config: dict, context_lengths) -> int:
    """Bytes of K and V that decode rows with these context lengths make the
    kernel read in one step, over all layers."""
    L, _, KV, Dh, window = _dims(config)
    b = BYTES[config.get("torch_dtype", "bfloat16")]
    tokens = sum(min(n, window) if window else n for n in context_lengths)
    return tokens * L * 2 * KV * Dh * b


def flops_per_step(config: dict, context_lengths) -> int:
    L, H, _, Dh, window = _dims(config)
    tokens = sum(min(n, window) if window else n for n in context_lengths)
    return 4 * tokens * H * Dh * L


def bound(config: dict, context_lengths, peaks: dict) -> tuple:
    """(least seconds per step, which of 'bandwidth' or 'compute' sets it)."""
    tb = kv_bytes_per_step(config, context_lengths) / peaks["hbm_bytes_per_s"]
    tc = flops_per_step(config, context_lengths) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
