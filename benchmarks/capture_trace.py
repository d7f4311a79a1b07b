#!/usr/bin/env python
"""Capture a jax.profiler trace of the flagship decode loop as committed
evidence of the compiled program structure.

The repo carries no device trace behind its perf claims (ROADMAP A2).
This script captures, on whatever backend JAX starts on, a profiler
trace directory showing the ONE
jit-compiled while-loop per decode call (zero Python per token — the
design claim every throughput number rests on), plus a JSON summary with
the raw per-rep timings. On TPU it additionally records device_kind so
the trace doubles as primary evidence for the tok/s measurements.

Usage: python benchmarks/capture_trace.py [--out traces/<name>]
       [--steps 8] [--reps 3]
Prints one JSON line; writes the trace under --out.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="trace dir (default: traces/<platform>_solo)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--model", default="tinyllama-1.1b")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    from distributed_llm_inference_tpu.engine import generate as G
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config

    dev = jax.devices()[0]
    platform = dev.platform
    out_dir = args.out or os.path.join(REPO, "traces", f"{platform}_solo")
    os.makedirs(out_dir, exist_ok=True)

    cfg = get_model_config(
        args.model,
        dtype="bfloat16" if platform == "tpu" else "float32",
        eos_token_id=-1,  # never early-exits: every rep runs exactly --steps
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray([[cfg.bos_token_id] + [7] * 127], jnp.int32)
    plen = jnp.int32(tokens.shape[1])
    sampling = G.default_sampling(greedy=True)
    kp, kd = jax.random.split(jax.random.PRNGKey(1))
    limit = jnp.int32(args.steps)

    # warm/compile outside the trace so the capture shows steady-state
    # dispatch: one XLA while-loop per decode call, no per-token Python
    cache = M.init_kv_cache(cfg, 1, max_seq=256)
    first, _, cache = G.prefill(cfg, params, tokens, plen, cache, kp, sampling)
    out, n_gen, cache = G.decode(
        cfg, params, first, cache, plen, limit, kd, sampling,
        max_steps=args.steps,
    )
    jax.block_until_ready(n_gen)

    per_rep = []
    with jax.profiler.trace(out_dir):
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out, n_gen, cache = G.decode(
                cfg, params, first, cache, plen, limit, kd, sampling,
                max_steps=args.steps,
            )
            jax.block_until_ready(n_gen)
            per_rep.append(round(time.perf_counter() - t0, 4))

    best = min(per_rep)
    result = {
        "artifact": "decode_trace",
        "model": cfg.name,
        "platform": platform,
        "device_kind": dev.device_kind,
        "dtype": cfg.dtype,
        "decode_steps": args.steps,
        "per_rep_s": per_rep,
        "tokens_per_sec_best": round(args.steps / best, 3),
        "trace_dir": os.path.relpath(out_dir, REPO),
    }
    line = json.dumps(result)
    print(line)
    with open(os.path.join(out_dir, "summary.json"), "w", encoding="utf-8") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
