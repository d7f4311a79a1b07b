"""What the delta-rule layers' state update must move and compute for a
launch (models/solar_open2.py, ops/delta_rule.py), from the configuration's
published sizes and the launch record's counts: `state_rows`, the row-steps
that read and write a state (a decode row a step, a prefill chunk once), and
the tokens they carry (`prefill_tokens` + the decode row-steps).

Bytes: a row-step reads and writes its float32 state, num_heads x head_dim x
head_dim x 4 B each way a KDA layer (every layer not in `gqa_layers`); a
token's q, k, v and its log decays g in (4 x num_heads x head_dim numbers),
its beta (num_heads) and its o out (num_heads x head_dim), in the served
dtype (the program hands them over in float32: not counted).
Operations: the recurrence's own, which is the least any form of the rule
does a token and head: the decay of the state (d_k x d_v), k^T S (2 d_k x
d_v), the rank-one fold (2 d_k x d_v) and the read S^T q (2 d_k x d_v): 7 x
d_k x d_v. The chunked form the program runs (three products with the state
a chunk, 6 d_k x d_v a token, the pair products inside a chunk and the
triangular solve) does more and none of its extra is counted, so the share
can only understate. The least time is the larger of bytes at the peak
bandwidth and operations at the bf16 peak (the program's float32 products at
`highest` precision are several passes of it: not counted either)."""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(config: dict):
    """The KDA layers' sizes, or None for a configuration without them."""
    lin = config.get("linear_attn_config")
    if not lin or "gqa_layers" not in config:
        return None
    H, D = lin["num_heads"], lin["head_dim"]
    return dict(H=H, Dk=D, Dv=D,
                layers=config["num_hidden_layers"] - len(config["gqa_layers"]),
                item=BYTES[config.get("torch_dtype", "bfloat16")])


def state_bytes(config: dict) -> int:
    """A row's float32 matrix state, one KDA layer."""
    s = sizes(config)
    return s["H"] * s["Dk"] * s["Dv"] * 4


def counts(launch: dict) -> tuple:
    """(row-steps that touch a state, the tokens they carry)."""
    rows = int(launch["state_rows"])
    tokens = rows - int(launch.get("prefill_chunks", 0)) \
        + int(launch.get("prefill_tokens", 0))
    return rows, tokens


def work(config: dict, launch: dict) -> tuple:
    """(useful bytes, operations) of one launch's state updates."""
    s = sizes(config)
    rows, tokens = counts(launch)
    H, Dk, Dv, L = s["H"], s["Dk"], s["Dv"], s["layers"]
    nbytes = L * (rows * 2 * state_bytes(config)
                  + tokens * (H * (3 * Dk + 2 * Dv) + H) * s["item"])
    return nbytes, L * tokens * H * 7 * Dk * Dv


def bound(config: dict, launches, peaks: dict) -> tuple:
    """(the least seconds for the launches' state updates, what bounds it)."""
    nbytes = flops = 0.0
    for launch in launches:
        b, f = work(config, launch)
        nbytes, flops = nbytes + b, flops + f
    tb = nbytes / peaks["hbm_bytes_per_s"]
    tc = flops / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
