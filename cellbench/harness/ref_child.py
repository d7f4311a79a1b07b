#!/usr/bin/env python3
"""The reference child: runs after the server has gone (a chip belongs to
one process at a time), makes the configuration's weights from the seed,
runs the plain reference teacher-forced over the check's sequences (prompt
+ the tokens the SERVER generated) and measures, for every generated
token, how far the reference's own logits put it below their best.

    python cellbench/harness/ref_child.py --config <file> --seed N --dir <check dir>

Reads <dir>/check_in.json (written by harness/check.py) and writes
<dir>/check_out.json. Margins only; the limits are in the configuration's
file and the judgement in harness/check.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)


def start(config_path: str, seed: int, cache_dir: str):
    """(config, reference module, params): JAX set up as the program sets it
    up (one compile cache), the weights made from the seed."""
    import jax
    import jax.numpy as jnp

    from harness.manifest import load_json, load_module

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config = load_json(config_path)
    ref = load_module("reference", config["reference"])
    params = ref.make_params(config, seed, jnp.dtype(config.get("dtype", "bfloat16")))
    jax.block_until_ready(params)
    return config, ref, params


def generated_logits(ref, config: dict, params, seq: dict):
    """The logits [rows, V] that predict the generated tokens of one check
    sequence: positions n_prompt-1 .. n-2 of the teacher-forced forward."""
    import numpy as np

    ids, p0 = seq["ids"], seq["n_prompt"] - 1
    x = ref.forward(config, params, ids)
    return np.asarray(ref.logits(config, params, x[p0:len(ids) - 1]))


def margins(lg, chosen) -> list:
    """For each row, the reference's best logit minus its logit of the token
    chosen, in units of the logits' standard deviation."""
    import numpy as np

    chosen = np.asarray(chosen)
    return [float(m) for m in (lg.max(axis=-1) - lg[np.arange(len(chosen)), chosen]) / lg.std()]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dir", required=True)
    ap.add_argument("--cache-dir", required=True)
    args = ap.parse_args()
    t0 = time.monotonic()
    config, ref, params = start(args.config, args.seed, args.cache_dir)
    t_params = time.monotonic() - t0

    import jax

    dev = jax.devices()[0]
    with open(os.path.join(args.dir, "check_in.json")) as f:
        spec = json.load(f)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "sequences": []}
    for seq in spec["sequences"]:
        lg = generated_logits(ref, config, params, seq)
        out["sequences"].append({
            "name": seq["name"], "tokens": len(seq["ids"]), "n_prompt": seq["n_prompt"],
            "margins": margins(lg, seq["ids"][seq["n_prompt"]:]), "logit_std": float(lg.std()),
        })
    out["seconds"] = {"params": round(t_params, 2), "total": round(time.monotonic() - t0, 2)}
    tmp = os.path.join(args.dir, "check_out.json.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(args.dir, "check_out.json"))


if __name__ == "__main__":
    main()
