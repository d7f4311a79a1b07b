"""What the paged attention kernels must move and compute in a model whose
window and global layers have their OWN K/V head counts and whose keys are
wider than its values (models/mimo_v2.py: `hybrid_layer_pattern`, 0 a global
layer and 1 a window layer), from the launch record's counts per kind:
`kv_tokens_global` / `kv_tokens_window`, the fewest KV positions per layer OF
THE KIND and K/V head the launch's rows must read, the window one clipped at
`sliding_window`. roofline/windowed_attention.py counts one head count and
one width for both kinds, which is Trinity's layer and not this one.

Bytes: a position's keys and values in a layer of a kind are that kind's K/V
heads x (head_dim + v_head_dim) numbers: the USEFUL lanes, 192 + 128. The
pool keeps a key row on 256 lanes (engine/paged.init_pool: whole lane
tiles); the 64 zero lanes a walk copies beside them are lost share, not
work. Operations: a query head's score over a position is 2 x head_dim, its
share of the value sum 2 x v_head_dim; all query heads, once a position (a
prefill chunk's queries each do this; the count takes one, as the record
does). The sink adds a term to a denominator and no byte. Both are lower
bounds, so the share can only understate."""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
KINDS = ("global", "window")  # hybrid_layer_pattern's 0 and 1


def sizes(config: dict) -> dict:
    """{kind: (layers, K/V heads)} and the widths, or None for a
    configuration of another family (no `hybrid_layer_pattern`)."""
    pattern = config.get("hybrid_layer_pattern")
    if pattern is None or "swa_num_key_value_heads" not in config:
        return None
    heads = {"global": config["num_key_value_heads"],
             "window": config["swa_num_key_value_heads"]}
    return {
        "kinds": {kind: (sum(int(p) == i for p in pattern), heads[kind])
                  for i, kind in enumerate(KINDS)},
        "Dk": config["head_dim"], "Dv": config["v_head_dim"],
        "H": config["num_attention_heads"],
        "item": BYTES[config.get("torch_dtype", "bfloat16")],
    }


def counts(config: dict, launch: dict):
    """(useful K/V bytes, score-and-value operations) of a launch, or None
    where the record does not count each kind."""
    s = sizes(config)
    if s is None or "kv_tokens_window" not in launch:
        return None
    nbytes = flops = 0
    for kind, (layers, kv_heads) in s["kinds"].items():
        positions = int(launch[f"kv_tokens_{kind}"]) * layers
        nbytes += positions * kv_heads * (s["Dk"] + s["Dv"]) * s["item"]
        flops += positions * s["H"] * 2 * (s["Dk"] + s["Dv"])
    return nbytes, flops


def bound(config: dict, launches, peaks: dict):
    """(the least seconds for the launches' K/V reads, what bounds it), or
    None where no launch counts each kind."""
    got = [c for c in (counts(config, launch) for launch in launches) if c]
    if not got:
        return None
    tb = sum(b for b, _ in got) / peaks["hbm_bytes_per_s"]
    tc = sum(f for _, f in got) / peaks["bf16_flops_per_s"]
    return (tb, "bandwidth") if tb >= tc else (tc, "compute")
