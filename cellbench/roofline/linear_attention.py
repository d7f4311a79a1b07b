"""What the decayed linear-attention scan must move and compute for a
launch (models/minicpm_sala.py, ops/linear_attention.py), from the launch
record's counts: `state_rows`, the row-steps that read and write a state
(a decode row a step, a prefill chunk once), and the tokens they carry
(`prefill_tokens` + the decode row-steps).

Bytes: a row-step reads and writes its float32 state, lightning_nh x
head_dim x head_dim x 4 B each way a `lightning-attn` layer; a token's q, k,
v in and o out, 4 x lightning_nh x head_dim numbers in the served dtype.
Operations: a token reads the state (2 x head_dim^2 a head) and adds to it
(2 x head_dim^2); within a chunk of m tokens each pair (t, u <= t) costs a
score and a value product, 4 x head_dim a head: m (m + 1) / 2 pairs, taken
with the chunks' tokens split evenly (the least the sum of squares can be).
Lower bounds both: the program's one-hot forms and its per-tile state
gathers do more."""

from __future__ import annotations

from roofline.weights import BYTES


def linear_layers(config: dict) -> int:
    return sum(kind == "lightning-attn" for kind in config["mixer_types"])


def counts(launch: dict) -> tuple:
    """(row-steps that touch a state, tokens, within-chunk pairs)."""
    rows = int(launch["state_rows"])
    chunks = int(launch.get("prefill_chunks", 0))
    chunk_tokens = int(launch.get("prefill_tokens", 0))
    tokens = rows - chunks + chunk_tokens
    pairs = rows - chunks  # a decode row's token sees itself
    if chunks:
        m = chunk_tokens / chunks
        pairs += chunks * m * (m + 1) / 2
    return rows, tokens, pairs


def state_bytes(config: dict) -> int:
    return config["lightning_nh"] * config["lightning_head_dim"] ** 2 * 4


def bound(config: dict, launches, peaks: dict) -> tuple:
    H, Dh, L = config["lightning_nh"], config["lightning_head_dim"], linear_layers(config)
    item = BYTES[config.get("torch_dtype", "bfloat16")]
    nbytes = flops = 0.0
    for launch in launches:
        rows, tokens, pairs = counts(launch)
        nbytes += L * (rows * 2 * state_bytes(config) + tokens * 4 * H * Dh * item)
        flops += L * H * (tokens * 4 * Dh * Dh + pairs * 4 * Dh)
    tb = nbytes / peaks["hbm_bytes_per_s"]
    tc = flops / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
