"""What the state-space layers' scan must move and compute for a launch
(models/granite_hybrid.py, ops/ssm_scan.py), from the launch record's
counts: `state_rows`, the row-steps that read and write a state (a decode
row a step, a prefill chunk once), and the tokens they carry
(`prefill_tokens` + the decode row-steps); and what a whole step of such a
model must stream: its weights, counted by `layer_types`, and those states.

Bytes of the scan: a row-step reads and writes its float32 state,
mamba_n_heads x mamba_d_head x mamba_d_state x 4 B each way a `mamba`
layer; a token's x in and y out (2 x d_inner numbers), its B and C
(2 x mamba_d_state) and its dt (mamba_n_heads) in the served dtype.
Operations: a token reads the state (2 x d_head x d_state a head) and adds
to it (2 x d_head x d_state); within a chunk of m tokens each pair
(t, u <= t) costs one C . B (2 x d_state, all heads') and a value product
a head (2 x d_head): m (m + 1) / 2 pairs, taken with the chunks' tokens
split evenly (the least the sum of squares can be). Lower bounds both: the
program's [heads, W, W] decay over the whole flat axis does more.

Bytes of a step: every matrix and vector of every layer once (a `mamba`
layer's W_in, taps, bias, dt_bias, A_log, D, norm and W_out; an `attention`
layer's W_q, W_k, W_v, W_o; every layer's FFN and two norms), the tied
table once as the head (the embedding's rows are gathered, not streamed)
and the last norm; NOT roofline/weights.py's dense formula, which would
count attention at every layer. Useful bytes only: no K/V, no activation."""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(config: dict) -> dict:
    H, P, N = (config[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state"))
    return dict(H=H, P=P, N=N, Di=H * P, K=config["mamba_d_conv"],
                C=H * P + 2 * config["mamba_n_groups"] * N,
                mamba=sum(k == "mamba" for k in config["layer_types"]),
                attention=sum(k == "attention" for k in config["layer_types"]),
                item=BYTES[config.get("torch_dtype", "bfloat16")])


def state_bytes(config: dict) -> int:
    """A row's float32 matrix state, one `mamba` layer."""
    s = sizes(config)
    return s["H"] * s["P"] * s["N"] * 4


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


def bound(config: dict, launches, peaks: dict) -> tuple:
    """(the least seconds for the launches' scans, what bounds it)."""
    s = sizes(config)
    H, P, N, L = s["H"], s["P"], s["N"], s["mamba"]
    nbytes = flops = 0.0
    for launch in launches:
        rows, tokens, pairs = counts(launch)
        nbytes += L * (rows * 2 * state_bytes(config)
                       + tokens * (2 * H * P + 2 * N + H) * s["item"])
        flops += L * (tokens * H * 4 * P * N + pairs * (2 * N + H * 2 * P))
    tb = nbytes / peaks["hbm_bytes_per_s"]
    tc = flops / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")


def step_weight_bytes(config: dict) -> int:
    """Bytes of weights one step of the model streams, by `layer_types`."""
    s = sizes(config)
    D, F = config["hidden_size"], config["shared_intermediate_size"]
    Hq, KV = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config.get("head_dim") or D // Hq
    mamba = (D * (2 * s["Di"] + 2 * s["N"] + s["H"]) + (s["K"] + 1) * s["C"]
             + s["Di"] + s["Di"] * D)
    attention = D * (Hq + 2 * KV) * Dh + Hq * Dh * D
    ffn = 3 * D * F + 2 * D
    n = (s["mamba"] * (mamba + ffn) + s["attention"] * (attention + ffn)
         + config["vocab_size"] * D + D)
    # (a mamba layer's dt_bias, A_log and D are float32)
    return n * s["item"] + s["mamba"] * 3 * s["H"] * 4


def step_bytes(config: dict, launch: dict) -> float:
    """The least bytes a launch's steps stream: the weights once a step the
    device runs (`steps_live` of a decode chunk, which ends with its last
    live row; 1 of a mixed step) and every row-step's state both ways."""
    steps = int(launch.get("steps_live", launch.get("steps", 1)))
    return (steps * step_weight_bytes(config)
            + int(launch["state_rows"]) * 2 * sizes(config)["mamba"]
            * state_bytes(config))
