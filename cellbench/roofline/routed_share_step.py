"""What a whole step of a model that holds ONE CHIP'S SHARE of its routed
experts must stream and compute (models/mimo_v2.py), from the
configuration's published sizes, the launch record and what the launch's
fetch learned of its routing. NOT roofline/weights.py's dense formula, which
would read every held expert every step.

Bytes a step the device runs (`steps_run` of a decode chunk where the fetch
says it, else the record's `steps_live`; 1 of a mixed step): every attention
matrix once (by `hybrid_layer_pattern`: a kind's W_q, W_k, W_v, W_o with its
own K/V heads), the dense layers' three FFN matrices, the routers, the
head's slice (`vocab_size` rows; the embedding's rows are gathered, not
streamed). Bytes a launch: the three matrices of every held expert the
launch TOUCHED (`moe_experts_touched`: experts x layers x steps that got a
token: a full fleet's 32 rows touch about 20 of 32 a layer) and the useful
K/V bytes (roofline/window_sink_attention.py). Operations: 2 a weight and
token for the matrices every token passes (the launch's `tokens_live`, or a
chunk's `row_steps`), 2 a weight and row-step for the head, 6 x hidden x
moe_intermediate a held token-expert pair (`moe_pairs`), and the scores and
value sums. The least time is the larger of bytes at the peak bandwidth and
operations at the bf16 peak, launch by launch. Useful work only (no
activation, no norm, no padding of the flat axis or of a group to the
grouped product's tiles, no zero lane of a key row), so a share it gives
cannot pass 100."""

from __future__ import annotations

from roofline import window_sink_attention as attention


def sizes(config: dict):
    """Parameters a step streams whatever it routes, and one expert's."""
    att = attention.sizes(config)
    if att is None or "moe_layer_freq" not in config:
        return None
    D, H, Dk, Dv = config["hidden_size"], att["H"], att["Dk"], att["Dv"]
    routed = sum(int(f) > 0 for f in config["moe_layer_freq"])
    dense = len(config["moe_layer_freq"]) - routed
    width = (config.get("expert_share") or {}).get(
        "router_width", config["n_routed_experts"])
    matrices = sum(layers * (D * (H * Dk + kv * (Dk + Dv)) + H * Dv * D)
                   for layers, kv in att["kinds"].values())
    matrices += dense * 3 * D * config["intermediate_size"] + routed * D * width
    return {
        "every_token": matrices, "head": config["vocab_size"] * D,
        "expert": 3 * D * config["moe_intermediate_size"], "item": att["item"],
    }


def counts(config: dict, launch: dict, after: dict):
    """(useful bytes, operations) of a launch, or None where the record or
    the fetch's span lacks what is counted."""
    s = sizes(config)
    kv = attention.counts(config, launch)
    if s is None or kv is None or "moe_pairs" not in after:
        return None
    mixed = launch.get("phase") == "mixed"
    steps = 1 if mixed else int(after.get(
        "steps_run", launch.get("steps_live", launch.get("steps", 1))))
    rows = int(launch.get("row_steps", 0))
    tokens = int(launch["tokens_live"]) if "tokens_live" in launch else rows
    head_rows = rows + int(launch.get("prefill_chunks", 0)) if mixed else rows
    touched, pairs = int(after["moe_experts_touched"]), int(after["moe_pairs"])
    nbytes = (steps * (s["every_token"] + s["head"]) + touched * s["expert"]) \
        * s["item"] + kv[0]
    flops = 2 * (tokens * s["every_token"] + head_rows * s["head"]
                 + pairs * s["expert"]) + kv[1]
    return nbytes, flops


def least_seconds(config: dict, launch: dict, after: dict, peaks: dict):
    got = counts(config, launch, after)
    if got is None:
        return None
    return max(got[0] / peaks["hbm_bytes_per_s"],
               got[1] / peaks["bf16_flops_per_s"])
