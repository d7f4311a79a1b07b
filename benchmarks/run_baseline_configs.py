#!/usr/bin/env python
"""Benchmark harness for the five BASELINE.json configs.

The reference publishes no benchmarks (SURVEY.md §6) — its only number is
the client-side note "100-125 seconds expected" for 15-20 tokens across
Colab VMs (/root/reference/Test.py:61). This harness measures OUR stack on
the five target configs:

  1. single-worker GPT-2-small, greedy, 128-tok prompt
  2. 2-stage pipeline: GPT-2-medium, greedy
  3. 4-stage pipeline: Llama-2-7B, greedy, HBM KV cache
  4. 8-stage pipeline: Llama-2-13B, top-p sampling, batch=1
  5. 8-stage microbatched (1F1B) pipeline: Llama-3-8B, batch=8

Two scales:
  --scale tiny  (default) CI-sized models of the same architecture family
                on an 8-device VIRTUAL CPU mesh — validates every config's
                parallel structure on any host, numbers are NOT chip perf.
  --scale full  the real models on real devices (a v5e-8 for configs 2-5);
                requires the devices and the HBM to exist.

Prints one JSON line per config:
  {"config": N, "desc": ..., "tokens_per_sec": ..., "ttft_s": ...,
   "aggregate_tokens_per_sec": ..., "scale": ..., "mesh": ..., ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force_cpu_mesh(n: int):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={n}"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"


# (desc, model_tiny, model_full, mesh kwargs, microbatches, batch, greedy)
CONFIGS = [
    ("single-worker GPT-2-small, greedy, 128-tok prompt",
     "test-gpt2-tiny", "gpt2-small", {}, 1, 1, True),
    ("2-stage pipeline: GPT-2-medium, greedy",
     "test-gpt2-tiny", "gpt2-medium", {"pp": 2}, 1, 1, True),
    ("4-stage pipeline: Llama-2-7B, greedy, HBM KV-cache",
     "test-llama-tiny", "llama2-7b", {"pp": 4}, 1, 1, True),
    ("8-stage pipeline: Llama-2-13B, top-p, batch=1",
     "test-llama-tiny", "llama2-13b", {"pp": 8}, 1, 1, False),
    ("8-stage microbatched 1F1B: Llama-3-8B, batch=8",
     "test-llama-tiny", "llama3-8b", {"pp": 8}, 8, 8, True),
]


def run_config(i, desc, model, mesh_kwargs, microbatches, batch, greedy,
               scale, prompt_len, steps):
    import jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu.config import MeshConfig
    from distributed_llm_inference_tpu.engine import generate as G
    from distributed_llm_inference_tpu.models.registry import get_model_config
    from distributed_llm_inference_tpu.runtime import create_backend

    pp = mesh_kwargs.get("pp", 1)
    cfg = get_model_config(model)
    if cfg.n_layers % max(pp, 1) != 0:
        # tiny models keep their family but need a pp-divisible depth
        cfg = cfg.replace(n_layers=max(pp, 1) * max(1, cfg.n_layers // max(pp, 1)))
    on_tpu = jax.default_backend() == "tpu"
    cfg = cfg.replace(dtype="bfloat16" if on_tpu else "float32", eos_token_id=-1)

    _, backend = create_backend(
        cfg, mesh_cfg=MeshConfig(**mesh_kwargs), microbatches=microbatches
    )

    max_seq = prompt_len + steps + 8
    tokens = jnp.asarray(
        [[cfg.bos_token_id] + [7] * (prompt_len - 1)] * batch, jnp.int32
    )
    plen = jnp.int32(prompt_len)
    sampling = G.default_sampling(
        temperature=0.7, top_k=0, top_p=0.9, greedy=greedy
    )
    kp, kd = jax.random.split(jax.random.PRNGKey(0))

    cache = backend.init_cache(batch, max_seq)
    # warm / compile
    first, logits, cache = backend.prefill(tokens, plen, cache, kp, sampling)
    out, n_gen, cache = backend.decode(
        first, cache, plen, jnp.int32(steps), kd, sampling, max_steps=steps
    )
    jax.block_until_ready(out)

    # TTFT: fresh prefill
    t0 = time.perf_counter()
    first, logits, cache = backend.prefill(tokens, plen, cache, kp, sampling)
    jax.block_until_ready(first)
    ttft = time.perf_counter() - t0

    # decode throughput
    t0 = time.perf_counter()
    out, n_gen, cache = backend.decode(
        first, cache, plen, jnp.int32(steps), kd, sampling, max_steps=steps
    )
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0

    per_stream = steps / dt
    print(json.dumps({
        "config": i + 1,
        "desc": desc,
        "model": cfg.name,
        "scale": scale,
        "mesh": {"pp": pp, "microbatches": microbatches},
        "batch": batch,
        "sampler": "greedy" if greedy else "top-p",
        "tokens_per_sec": round(per_stream, 3),
        "aggregate_tokens_per_sec": round(per_stream * batch, 3),
        "ttft_s": round(ttft, 4),
        "decode_steps": steps,
        "prompt_len": prompt_len,
        "platform": jax.default_backend(),
    }), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--configs", default="1,2,3,4,5",
                    help="comma-separated subset, e.g. 1,3")
    ap.add_argument("--steps", type=int, default=0,
                    help="decode steps (default: 32 tiny / 64 full)")
    args = ap.parse_args(argv)

    if args.scale == "tiny":
        _force_cpu_mesh(8)
    steps = args.steps or (32 if args.scale == "tiny" else 64)
    prompt_len = 32 if args.scale == "tiny" else 128

    wanted = {int(x) for x in args.configs.split(",")}
    for i, (desc, tiny, full, mesh_kwargs, mb, batch, greedy) in enumerate(CONFIGS):
        if i + 1 not in wanted:
            continue
        model = tiny if args.scale == "tiny" else full
        run_config(i, desc, model, mesh_kwargs, mb, batch, greedy,
                   args.scale, prompt_len, steps)


if __name__ == "__main__":
    sys.exit(main())
