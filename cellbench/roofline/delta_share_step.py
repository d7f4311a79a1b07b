"""What a whole step of the delta-rule / attention hybrid that holds ONE
CHIP'S SHARE of its routed experts must stream and compute
(models/solar_open2.py), from the configuration's published sizes, the
launch record and what the launch's fetch learned of its routing. NOT
roofline/weights.py's dense formula, which would read every held expert
every step and count attention at every layer.

Bytes a step the device runs (`steps_run` of a decode chunk where the fetch
says it, else the record's `steps_live`; 1 of a mixed step): every mixer's
matrices once (a KDA layer's W_q, W_k, W_v, W_o, its two low-rank pairs,
W_beta and taps; a GQA layer's W_q, W_k, W_v, W_gate, W_o), every layer's
router and shared expert, the head's slice (`vocab_size` rows; the
embedding's rows are gathered, not streamed). Bytes a launch: the three
matrices of every held expert the launch TOUCHED (`moe_experts_touched`:
experts x layers x steps that got a token), every state row-step's float32
state both ways (`state_rows` x 2 x the KDA layers' state,
roofline/delta_rule.py) and the useful K/V bytes (`kv_tokens` x the GQA
layers' K and V). Operations: 2 a weight and token for the matrices every
token passes (the launch's `tokens_live`, or a chunk's `row_steps`), 2 a
weight and row-step for the head, 6 x hidden x moe_intermediate a held
token-expert pair (`moe_pairs`), the attention scores and value sums, and
the delta rule's own (roofline/delta_rule.py). The least time is the larger
of bytes at the peak bandwidth and operations at the bf16 peak, launch by
launch. Useful work only (no activation, no norm, no padding of the flat
axis or of a group to the grouped product's tiles), so a share it gives
cannot pass 100."""

from __future__ import annotations

from roofline import delta_rule


def sizes(config: dict):
    """Parameters a step streams whatever it routes, and one expert's."""
    d = delta_rule.sizes(config)
    if d is None or "n_routed_experts" not in config:
        return None
    D, L = config["hidden_size"], config["num_hidden_layers"]
    H, KV, Dh = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    Hd, r = d["H"] * d["Dk"], d["Dk"]
    K = config["linear_attn_config"]["short_conv_kernel_size"]
    kda = D * (3 * Hd + 2 * r + d["H"]) + 2 * r * Hd + Hd * D + K * 3 * Hd
    gqa = D * (2 * H * Dh + 2 * KV * Dh) + H * Dh * D
    Fm = config["moe_intermediate_size"]
    width = (config.get("expert_share") or {}).get(
        "router_width", config["n_routed_experts"])
    every = (d["layers"] * kda + (L - d["layers"]) * gqa
             + L * (D * width + 3 * D * config["n_shared_experts"] * Fm))
    return {
        "every_token": every, "head": config["vocab_size"] * D,
        "expert": 3 * D * Fm, "item": d["item"],
        "kv_row": (L - d["layers"]) * 2 * KV * Dh * d["item"],
        "kv_flops": (L - d["layers"]) * H * 4 * Dh,
    }


def counts(config: dict, launch: dict, after: dict):
    """(useful bytes, operations) of a launch, or None where the record or
    the fetch's span lacks what is counted."""
    s = sizes(config)
    if s is None or "moe_pairs" not in after or "state_rows" not in launch \
            or "kv_tokens" not in launch:
        return None
    mixed = launch.get("phase") == "mixed"
    steps = 1 if mixed else int(after.get(
        "steps_run", launch.get("steps_live", launch.get("steps", 1))))
    rows = int(launch.get("row_steps", 0))
    tokens = int(launch["tokens_live"]) if "tokens_live" in launch else rows
    head_rows = rows + int(launch.get("prefill_chunks", 0)) if mixed else rows
    touched, pairs = int(after["moe_experts_touched"]), int(after["moe_pairs"])
    kv = int(launch["kv_tokens"])
    state_bytes, state_flops = delta_rule.work(config, launch)
    nbytes = (steps * (s["every_token"] + s["head"]) + touched * s["expert"]) \
        * s["item"] + kv * s["kv_row"] + state_bytes
    flops = 2 * (tokens * s["every_token"] + head_rows * s["head"]
                 + pairs * s["expert"]) + kv * s["kv_flops"] + state_flops
    return nbytes, flops


def least_seconds(config: dict, launch: dict, after: dict, peaks: dict):
    got = counts(config, launch, after)
    if got is None:
        return None
    return max(got[0] / peaks["hbm_bytes_per_s"],
               got[1] / peaks["bf16_flops_per_s"])
