"""What the routed experts' grouped matrix products (models/mla_moe.py
`routed_expert_matmul`: three a layer, gate, up, down) must move and
compute, from the configuration's published sizes.

An expert that got at least one token has its three matrices read once a
scheduler step: 3 x hidden x moe_intermediate numbers. A token-expert pair
costs 2 operations a weight: 6 x hidden x moe_intermediate. Both are lower
bounds (no activations, no padding of a group to the kernel's 128-pair
tiles, an expert whose tokens straddle two tiles read twice), so a share
they give can only understate."""

from __future__ import annotations

from roofline.weights import BYTES


def expert_bytes(config: dict) -> int:
    """Bytes of one expert's three matrices."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * BYTES[config.get("torch_dtype", "bfloat16")])


def pair_flops(config: dict) -> int:
    """Operations of one token through one expert."""
    return 6 * config["hidden_size"] * config["moe_intermediate_size"]


def bound(config: dict, touched: int, pairs: int, peaks: dict) -> tuple:
    """(least seconds, which of 'bandwidth' or 'compute' sets it) for
    `touched` expert reads (experts x layers x steps that got a token) and
    `pairs` token-expert pairs."""
    tb = touched * expert_bytes(config) / peaks["hbm_bytes_per_s"]
    tc = pairs * pair_flops(config) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
