#!/usr/bin/env python
"""Headline benchmark: TinyLlama-1.1B autoregressive decode throughput.

Apples-to-apples with the reference's own observed number on the same
model (`TinyLlama/TinyLlama-1.1B-Chat-v1.0`): ~0.12-0.2 tokens/sec end to
end across 3 Colab CPU VMs with no KV cache and 4 JSON-over-WAN activation
transfers per token (/root/reference/Test.py:61, orchestration.py:202).
Baseline pinned at the midpoint, 0.16 tok/s.

Here the same architecture runs as one jit-compiled program on one TPU
chip: bf16 params in HBM, prefill in a single call, decode as an on-device
while-loop with a donated KV cache. Weights are random-init (zero network
egress; throughput is weight-value independent).

One process, one TPU: the script initialises JAX in this process, fails
with a non-zero exit code when the device is not a TPU (a number from the
CPU backend under a device metric's name is worse than no number), and lets
any failure of the headline measurement end the run. The legs below are what
twenty PRs accumulated; ROADMAP A1 / C1 replace them with cells.

Prints the headline JSON line as soon as it exists, then the enriched one:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N,
   ...extras}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

REFERENCE_TOK_S = 0.16  # midpoint of the reference's 0.12-0.2 tok/s
PROMPT_LEN = 128
DECODE_STEPS = 64
# skip the optional batch-8 leg when the single-stream part (compiles
# included) has already used this much wall clock
BATCH_LEG_DEADLINE_S = 420.0
T_START = time.perf_counter()

# Peak dense bf16 FLOP/s and HBM bandwidth (bytes/s) per chip, keyed by
# substring of device_kind. Used for the MFU / bandwidth-utilization
# estimates; unknown kinds report null. Batch-1 decode is HBM-bound (every
# step streams all params from HBM once), so `hbm_util` is the roofline
# that actually judges single-stream speed; MFU judges the batched leg.
_PEAK = [
    ("v5 lite", 197e12, 819e9),  # v5e
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v6 lite", 918e12, 1640e9),  # trillium
    ("v6e", 918e12, 1640e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 46e12, 700e9),
]


def _emit(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


# 1F1B microbatched-pipeline leg: runs in its own subprocess on a
# 2-virtual-CPU-device mesh (see the call site for why). Prints one JSON
# line with the aggregate decode throughput of a 4-row fleet riding the
# zero-bubble schedule (2 stages x 2 microbatches chasing each other
# around the ppermute ring — parallel/schedule.py).
_MB_LEG_SRC = """
import json, os, time
import jax
import jax.numpy as jnp
import numpy as np
from distributed_llm_inference_tpu import MeshConfig, get_model_config
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.runtime import create_backend

cfg = get_model_config("test-llama-tiny", dtype="float32", eos_token_id=-1)
cfg, be = create_backend(cfg, mesh_cfg=MeshConfig(pp=2), microbatches=2)
B, PLEN, BUCKET, STEPS = 4, 24, 32, 16
row = [cfg.bos_token_id] + [7] * (PLEN - 1) + [cfg.pad_token_id] * (BUCKET - PLEN)
tokens = jnp.asarray([row] * B, jnp.int32)
plen = jnp.int32(PLEN)
sampling = G.default_sampling(greedy=True)
kp, kd = jax.random.split(jax.random.PRNGKey(0))
limit = jnp.int32(STEPS)

cache = be.init_cache(B, 128)
first, _, cache = be.prefill(tokens, plen, cache, kp, sampling)
out, n_gen, cache = be.decode(
    first, cache, plen, limit, kd, sampling, max_steps=STEPS
)
np.asarray(n_gen)  # warm/compile + drain

def rep():
    global cache
    t0 = time.perf_counter()
    _, n, cache = be.decode(
        first, cache, plen, limit, kd, sampling, max_steps=STEPS
    )
    np.asarray(n)
    return time.perf_counter() - t0

t = min(rep() for _ in range(3))
print(json.dumps({
    "tokens_per_sec": round(B * STEPS / t, 3), "batch": B, "steps": STEPS,
    "pp": 2, "microbatches": 2, "model": cfg.name,
}))
"""


# comms-contract cross-check leg (analysis/comms.py): run real pp=2
# prefill + decode launches with the wire knob off and on, read the
# dli_pp_wire_bytes_total per-path deltas a MetricsRegistry actually
# accumulated, and recompute the same launches through the symbolic link
# table. The two MUST agree to the byte — the runtime accounting routes
# through the table (parallel/pipeline.py _account_link), so a mismatch
# means the static model lies about what the wire carries.
_COMMS_LEG_SRC = """
import json, os
import jax
import jax.numpy as jnp
import numpy as np
from distributed_llm_inference_tpu import MeshConfig, get_model_config
from distributed_llm_inference_tpu.analysis import comms
from distributed_llm_inference_tpu.engine import generate as G
from distributed_llm_inference_tpu.runtime import create_backend
from distributed_llm_inference_tpu.utils.metrics import MetricsRegistry

B, PLEN, BUCKET, STEPS = 2, 24, 32, 8
out = {"modes": {}, "exact_agreement": True, "pp": 2,
       "model": "test-llama-tiny"}
for mode, wq in (("off", None), ("on", "int8")):
    cfg = get_model_config(
        "test-llama-tiny", dtype="float32", eos_token_id=-1
    )
    cfg, be = create_backend(
        cfg, mesh_cfg=MeshConfig(pp=2), wire_quant=wq
    )
    reg = MetricsRegistry()
    be.attach_wire_metrics(reg)
    row = ([cfg.bos_token_id] + [7] * (PLEN - 1)
           + [cfg.pad_token_id] * (BUCKET - PLEN))
    tokens = jnp.asarray([row] * B, jnp.int32)
    sampling = G.default_sampling(greedy=True)
    kp, kd = jax.random.split(jax.random.PRNGKey(0))
    cache = be.init_cache(B, 128)
    first, _, cache = be.prefill(
        tokens, jnp.int32(PLEN), cache, kp, sampling
    )
    _, n_gen, cache = be.decode(
        first, cache, jnp.int32(PLEN), jnp.int32(STEPS), kd, sampling,
        max_steps=STEPS,
    )
    np.asarray(n_gen)
    fam = reg.get("dli_pp_wire_bytes_total")
    measured = {
        path: int(fam.labels(path=path).value)
        for path in ("microstep", "broadcast")
    }
    q = wq is not None
    p = comms.params_from_config(
        cfg, dp=1, pp=2, rows=B, t=BUCKET, steps=STEPS
    )
    derived = {
        "microstep":
            comms.link_bytes("pp-microstep-prefill", p, itemsize=4, quant=q)
            + comms.link_bytes("pp-microstep-decode", p, itemsize=4, quant=q),
        "broadcast":
            comms.link_bytes("pp-broadcast-prefill", p, itemsize=4, quant=q)
            + comms.link_bytes("pp-broadcast-decode", p, itemsize=4, quant=q),
    }
    agree = measured == derived
    out["modes"][mode] = {
        "measured": measured, "derived": derived, "agree": agree,
    }
    out["exact_agreement"] = out["exact_agreement"] and agree
assert out["exact_agreement"], out
print(json.dumps(out))
"""


# MPMD stage-pipeline leg (serving/stage_runtime.py): a REAL 2-process
# stage fleet — each stage a subprocess owning a contiguous layer slice,
# activations over the HTTP stage transport — driven against the
# single-process forward loop on the same seed-0 weights. Headlines:
# TTFT/TPOT p99 per topology (the cross-process hop tax on a CPU proxy;
# on TPU the transport is device-to-device and the tax is ICI-bound),
# bit-identity of the transcripts, and the fault-containment numbers the
# chaos suite asserts but never times: kill -9 the last stage mid-decode
# and measure time-to-recover (faulted wall minus clean wall) plus
# tokens recomputed, warm (block shadow restored) vs cold (shadow
# wiped). Runs in its own subprocess like the 1f1b leg so the stage
# fleet's env never perturbs this process's measurements.
_MPMD_LEG_SRC = """
import json, os, shutil, tempfile, time
import jax
import jax.numpy as jnp
from distributed_llm_inference_tpu.models import api as M
from distributed_llm_inference_tpu.models.registry import get_model_config
from distributed_llm_inference_tpu.serving.stage_runtime import (
    HttpStageTransport, MPMDPipeline, StageSupervisor, free_port,
)
from distributed_llm_inference_tpu.utils.tokenizer import ByteTokenizer

MODEL, BLOCK, STAGES, N_NEW, KILL_AFTER = "test-llama-tiny", 8, 2, 16, 6
PROMPTS = ["mpmd bench prompt %d!" % i for i in range(2)]
REC_PROMPT = "mpmd recovery probe"

def p99(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(0.99 * len(xs)))]

stage_env = dict(os.environ, JAX_PLATFORMS="cpu")
stage_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
stage_env.pop("DLI_FAULTS", None)
restore = tempfile.mkdtemp(prefix="bench_mpmd_")
sup = StageSupervisor(
    MODEL, STAGES, [free_port() for _ in range(STAGES)], seed=0,
    block_size=BLOCK, restore_dir=restore, restart_budget=100,
    env=stage_env,
)
pipe = MPMDPipeline(sup, transport=HttpStageTransport())
out = {"stages": STAGES, "model": MODEL, "block_size": BLOCK}
try:
    t0 = time.perf_counter()
    pipe.start_fleet(ready_timeout_s=180)
    out["fleet_spawn_s"] = round(time.perf_counter() - t0, 2)
    pipe.generate(PROMPTS[0], 4)  # compile every stage's programs

    ttfts, itls, pipe_texts = [], [], []
    for p in PROMPTS:
        t0 = time.perf_counter()
        rid = pipe.start(p)
        ttfts.append(time.perf_counter() - t0)
        for _ in range(N_NEW - 1):
            t1 = time.perf_counter()
            if pipe.step_once(rid) is None:
                break
            itls.append(time.perf_counter() - t1)
        pipe_texts.append(pipe.finish(rid)["tokens"])
    out["pipeline"] = {
        "ttft_p99_s": round(p99(ttfts), 4),
        "tpot_p99_s": round(p99(itls), 5),
        "tokens_per_sec": round(len(itls) / sum(itls), 2),
    }

    # single-process baseline: same model, same seed-0 weights, the plain
    # forward loop the chaos tests use as their bit-identity reference
    cfg = get_model_config(MODEL)
    tok = ByteTokenizer()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def solo(prompt):
        ids = tok.encode(prompt)
        cache = M.init_kv_cache(cfg, 1, cfg.max_seq_len, cfg.n_layers)
        t0 = time.perf_counter()
        logits, cache = M.forward(
            cfg, params, jnp.asarray([ids], jnp.int32), cache, 0
        )
        t = int(jnp.argmax(logits[0, -1]))
        ttft = time.perf_counter() - t0
        toks, pos, itl = [t], len(ids), []
        for _ in range(N_NEW - 1):
            if t == tok.eos_token_id:
                break
            t1 = time.perf_counter()
            logits, cache = M.forward(
                cfg, params, jnp.asarray([[t]], jnp.int32), cache, pos
            )
            t = int(jnp.argmax(logits[0, -1]))
            itl.append(time.perf_counter() - t1)
            toks.append(t)
            pos += 1
        if toks and toks[-1] == tok.eos_token_id:
            toks = toks[:-1]
        return ttft, itl, toks

    solo(PROMPTS[0])  # compile
    s_ttfts, s_itls, solo_texts = [], [], []
    for p in PROMPTS:
        a, b, c = solo(p)
        s_ttfts.append(a)
        s_itls.extend(b)
        solo_texts.append(c)
    out["single_process"] = {
        "ttft_p99_s": round(p99(s_ttfts), 4),
        "tpot_p99_s": round(p99(s_itls), 5),
        "tokens_per_sec": round(len(s_itls) / sum(s_itls), 2),
    }
    out["bit_identical_vs_single_process"] = pipe_texts == solo_texts
    out["pipeline_tpot_overhead"] = round(
        out["pipeline"]["tpot_p99_s"] / out["single_process"]["tpot_p99_s"],
        2,
    )

    # fault containment, timed: kill -9 the last stage mid-decode.
    # time_to_recover = faulted wall minus the clean wall of the
    # IDENTICAL request, so the number isolates salvage (respawn +
    # restore + replay); tokens_recomputed comes off last_salvage().
    def request(kill=False, wipe=False):
        t0 = time.perf_counter()
        rid = pipe.start(REC_PROMPT)
        for step in range(N_NEW - 1):
            if kill and step == KILL_AFTER:
                victim = STAGES - 1
                sup.proc(victim).kill()
                sup.proc(victim).wait(timeout=10)
                if wipe:
                    shutil.rmtree(
                        os.path.join(restore, "stage%d" % victim),
                        ignore_errors=True,
                    )
            if pipe.step_once(rid) is None:
                break
        toks = pipe.finish(rid)["tokens"]
        return time.perf_counter() - t0, toks, rid

    clean_s, clean_toks, _ = request()
    rec = {"clean_request_s": round(clean_s, 3)}
    for mode, wipe in (("warm", False), ("cold", True)):
        wall, toks, rid = request(kill=True, wipe=wipe)
        sal = pipe.last_salvage()
        rec[mode] = {
            "ok": toks == clean_toks and sal["stage"] == STAGES - 1,
            "time_to_recover_s": round(max(0.0, wall - clean_s), 3),
            "tokens_recomputed": sal["tokens_recomputed"].get(rid),
            "salvage_s": round(sal["secs"], 3),
        }
    # the recovery CLAIM on the CPU proxy is tokens_recomputed (warm
    # replays only the partial tail block, cold the whole fed prefix):
    # per-step wall here is jit-dispatch + HTTP-hop bound (~1 s), so the
    # faulted-minus-clean wall delta is noise-bounded and the wall
    # speedup is only reported when both deltas actually resolved
    if rec["warm"]["tokens_recomputed"]:
        rec["cold_vs_warm_recompute"] = round(
            rec["cold"]["tokens_recomputed"]
            / rec["warm"]["tokens_recomputed"], 1,
        )
    if (rec["warm"]["time_to_recover_s"] > 0.3
            and rec["cold"]["time_to_recover_s"] > 0.3):
        rec["warm_recovery_speedup"] = round(
            rec["cold"]["time_to_recover_s"]
            / rec["warm"]["time_to_recover_s"], 2,
        )
    out["recovery"] = rec
finally:
    pipe.shutdown()
    shutil.rmtree(restore, ignore_errors=True)
print(json.dumps(out))
"""


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def run_benchmark():
    import jax

    from distributed_llm_inference_tpu.utils import compile_cache

    compile_cache.enable()
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_inference_tpu.engine import generate as G
    from distributed_llm_inference_tpu.models import api as M
    from distributed_llm_inference_tpu.models.registry import get_model_config

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found {platform!r} "
            f"({dev.device_kind}). Run it on the chip."
        )
    decode_steps = DECODE_STEPS
    n_chain = 4
    n_reps = 3
    # eos_token_id=-1: no token id can match, so the decode loop never
    # early-exits — every run measures exactly decode_steps steps.
    cfg = get_model_config(
        "tinyllama-1.1b",
        dtype="bfloat16",
        eos_token_id=-1,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    n_params = int(
        sum(x.size for x in jax.tree_util.tree_leaves(params))
    )

    tokens = jnp.asarray(
        [[cfg.bos_token_id] + [7] * (PROMPT_LEN - 1)], jnp.int32
    )
    plen = jnp.int32(PROMPT_LEN)
    sampling = G.default_sampling(greedy=True)
    kp, kd = jax.random.split(jax.random.PRNGKey(1))
    limit = jnp.int32(decode_steps)

    # Every timing ends in a device->host fetch of a value the timed work
    # produced: the fetch is a host sync, so the clock stops when the
    # device has finished, not when the calls were enqueued.
    def fetch(x):
        return np.asarray(x)

    # warm-up: compile prefill + decode, drain the queue
    cache = M.init_kv_cache(cfg, 1, max_seq=512)
    first, _, cache = G.prefill(cfg, params, tokens, plen, cache, kp, sampling)
    out, n_gen, cache = G.decode(
        cfg, params, first, cache, plen, limit, kd, sampling,
        max_steps=decode_steps,
    )
    fetch(n_gen)

    # TTFT: K back-to-back prefills (each re-initing its cache) ending in
    # ONE scalar fetch, divided by K.
    KP = 4

    def prefill_chain():
        f = None
        for _ in range(KP):
            c = M.init_kv_cache(cfg, 1, max_seq=512)
            f, _, c = G.prefill(cfg, params, tokens, plen, c, kp, sampling)
        fetch(f)

    prefill_chain()  # warm (compile already done above; drain queue)
    ttft = min(_timed(prefill_chain)[0] for _ in range(3)) / KP
    # prefill is the COMPUTE-bound phase (decode is HBM-bound): its MFU
    # judges how well the big batched matmuls land on the MXU
    prefill_tok_s = PROMPT_LEN / ttft if ttft > 0 else None

    # decode throughput: K chained decode calls (donated cache threaded
    # through), one scalar fetch at the end. One timing helper serves the
    # baseline, batch, and int8 legs so the discipline (rep count) can
    # never drift between them.
    K = n_chain

    def time_decode(p, first_tok, c):
        def run():
            nonlocal c
            for _ in range(K):
                _, n_gen, c = G.decode(
                    cfg, p, first_tok, c, plen, limit, kd, sampling,
                    max_steps=decode_steps,
                )
            fetch(n_gen)

        per_call = max(
            min(_timed(run)[0] for _ in range(n_reps)), 1e-9
        ) / K
        return decode_steps / per_call, c

    tok_s, cache = time_decode(params, first, cache)

    # MFU: dense-decode FLOPs are ~2*params per token; judged against the
    # chip's peak bf16 FLOP/s. Decode is HBM-bandwidth-bound, so low single
    # digits is the expected healthy range for batch 1 — hbm_util (bytes
    # streamed per token ≈ 2*params bf16, vs peak HBM bandwidth) is the
    # roofline batch-1 decode is actually racing.
    peak = peak_bw = None
    kind = dev.device_kind.lower()
    for sub, flops, bw in _PEAK:
        if sub in kind:
            peak, peak_bw = flops, bw
            break
    bytes_per_param = 2 if cfg.dtype == "bfloat16" else 4
    mfu = (2.0 * n_params * tok_s / peak) if peak else None
    hbm_util = (
        bytes_per_param * n_params * tok_s / peak_bw if peak_bw else None
    )

    result = {
        "metric": "tinyllama_1.1b_decode_throughput",
        "value": round(tok_s, 3),
        "unit": "tokens/sec",
        "vs_baseline": round(tok_s / REFERENCE_TOK_S, 1),
        "ttft_s": round(ttft, 4),
        "prompt_len": PROMPT_LEN,
        "decode_steps": decode_steps,
        "platform": platform,
        "device_kind": dev.device_kind,
        "dtype": cfg.dtype,
        "n_params": n_params,
        "mfu": round(mfu, 5) if mfu is not None else None,
        "hbm_util": round(hbm_util, 4) if hbm_util is not None else None,
    }
    if peak and prefill_tok_s:
        result["prefill_mfu"] = round(2.0 * n_params * prefill_tok_s / peak, 4)
    # the headline lands the moment it exists; the final emit below
    # re-prints the enriched result and a consumer takes the LAST line
    _emit(result)

    # wire-quant leg (quantized inter-stage transfers, ops/wire_quant.py
    # + EngineConfig.pp_wire_quant): the pp proxy — greedy decode with
    # the pp ring's wire numerics replayed on one device (one int8
    # round trip per stage hand-off + the final-stage broadcast), quant
    # on vs off. Headlines: wire bytes/token per ICI link (STATIC — the
    # quantity the knob shrinks, and what binds deep pipelines on a real
    # slice), the teacher-forced greedy match rate (the quality side of
    # the trade, same gate tests/test_wire_quant.py asserts), and proxy
    # tok/s on vs off. The CPU proxy PAYS the quantize FLOPs and
    # collects none of the ICI-byte win, so the tok/s ratio structurally
    # understates a TPU — the bytes/token reduction is the claim.
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            from distributed_llm_inference_tpu.ops import wire_quant as _WQ

            w_cfg = get_model_config(
                "test-llama-tiny", dtype="float32", eos_token_id=-1,
                max_seq_len=512,
            )
            w_params = M.init_params(w_cfg, jax.random.PRNGKey(2))
            w_S, w_N = 4, 24
            w_rng = np.random.default_rng(7)
            w_prompts = [
                w_rng.integers(3, w_cfg.vocab_size, size=16).tolist()
                for _ in range(6)
            ]
            w_rates = [
                _WQ.proxy_stage_match(w_cfg, w_params, p, w_N, w_S)
                for p in w_prompts
            ]

            def _wire_tok_s(quant):
                _WQ.proxy_stage_generate(
                    w_cfg, w_params, w_prompts[0], w_N, w_S, quant=quant
                )  # compile
                t0 = time.perf_counter()
                n = 0
                for p in w_prompts[:4]:
                    n += len(_WQ.proxy_stage_generate(
                        w_cfg, w_params, p, w_N, w_S, quant=quant
                    ))
                return n / (time.perf_counter() - t0)

            tok_off = _wire_tok_s(False)
            tok_on = _wire_tok_s(True)
            act = (1, 1, w_cfg.dim)
            hops = w_S + 1  # S ring hops + the masked-psum broadcast
            bpt_off = _WQ.wire_bytes(act, 4, hops, quant=False)
            bpt_on = _WQ.wire_bytes(act, 4, hops, quant=True)
            result["wire_quant"] = {
                "proxy_stages": w_S,
                "model": w_cfg.name,
                "wire_bytes_per_token_off": bpt_off,
                "wire_bytes_per_token_on": bpt_on,
                "wire_bytes_reduction": round(bpt_off / bpt_on, 3),
                "greedy_match_rate_mean": round(
                    float(np.mean(w_rates)), 4
                ),
                "greedy_match_rate_min": round(min(w_rates), 4),
                "proxy_tok_s_off": round(tok_off, 2),
                "proxy_tok_s_on": round(tok_on, 2),
                "proxy_tok_s_ratio": round(tok_on / tok_off, 3),
                "note": (
                    "bytes/token per ICI link, static from shapes; the "
                    "CPU proxy pays the quantize FLOPs and none of the "
                    "ICI win, so tok_s_ratio understates a TPU slice"
                ),
            }
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)


    # batched decode: 8 identical streams through the raw backend decode
    # loop (NOT the engine's generate_batch ragged path — this measures the
    # aggregate-throughput ceiling batching exposes, with no left-pad
    # masking in the program). Weights stream from HBM once per step
    # regardless of batch, so aggregate throughput scales ~linearly until
    # compute-bound. The prefilled B=1 cache is tiled instead of compiling
    # a batched prefill (identical rows; only the decode program costs a
    # compile), and the leg is skipped entirely if the single-stream part
    # already ate the time budget — the primary metric must always land.
    batch_tok_s = None
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        BATCH = 8
        first_b = jnp.tile(first, (BATCH,))
        cache_b = jax.tree.map(
            lambda x: jnp.tile(x, (1, BATCH) + (1,) * (x.ndim - 2)), cache
        )
        out, n_gen_b, cache_b = G.decode(
            cfg, params, first_b, cache_b, plen, limit, kd, sampling,
            max_steps=decode_steps,
        )
        fetch(n_gen_b)  # warm/compile
        per_stream, cache_b = time_decode(params, first_b, cache_b)
        batch_tok_s = BATCH * per_stream
        result["batch8_tokens_per_sec"] = round(batch_tok_s, 3)
        if peak:
            result["batch8_mfu"] = round(2.0 * n_params * batch_tok_s / peak, 5)

    # int8 weight-only leg (ops/quant.py): same decode, half the HBM
    # bytes/token — the lever that moves the bandwidth roofline itself.
    # Skipped under the same wall-clock budget discipline as the batch leg.
    int8_tok_s = None
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        from distributed_llm_inference_tpu.ops.quant import quantize_params

        qparams = quantize_params(cfg, params)
        cache_q = M.init_kv_cache(cfg, 1, max_seq=512)
        first_q, _, cache_q = G.prefill(
            cfg, qparams, tokens, plen, cache_q, kp, sampling
        )
        out, n_gen_q, cache_q = G.decode(
            cfg, qparams, first_q, cache_q, plen, limit, kd, sampling,
            max_steps=decode_steps,
        )
        fetch(n_gen_q)  # warm/compile
        int8_tok_s, cache_q = time_decode(qparams, first_q, cache_q)
        del qparams, cache_q
        result["int8_tokens_per_sec"] = round(int8_tok_s, 3)
        if peak_bw:
            # int8 streams ~1 byte/param (+0.2% scales)
            result["int8_hbm_util"] = round(
                1.0 * n_params * int8_tok_s / peak_bw, 4
            )

    # int4 leg (packed nibbles + Pallas VMEM-unpack kernel): halves the
    # weight bytes again. Fully fenced — compile/kernel failure must
    # never cost the primary metric.
    int4_tok_s = None
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            from distributed_llm_inference_tpu.ops.quant import (
                quantize_params as _qp,
            )

            q4params = _qp(cfg, params, mode="int4")
            cache_q4 = M.init_kv_cache(cfg, 1, max_seq=512)
            first_q4, _, cache_q4 = G.prefill(
                cfg, q4params, tokens, plen, cache_q4, kp, sampling
            )
            out, n_gen_q4, cache_q4 = G.decode(
                cfg, q4params, first_q4, cache_q4, plen, limit, kd, sampling,
                max_steps=decode_steps,
            )
            fetch(n_gen_q4)  # warm/compile
            int4_tok_s, cache_q4 = time_decode(q4params, first_q4, cache_q4)
            result["int4_tokens_per_sec"] = round(int4_tok_s, 3)
            if peak_bw:
                # int4 streams ~0.5 byte/param (+ per-group scales)
                result["int4_hbm_util"] = round(
                    0.5 * n_params * int4_tok_s / peak_bw, 4
                )
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # flash-attention prefill leg: the Pallas kernel (ops/flash_attention)
    # vs the XLA einsum path at a 1k prompt — prefill is where attention
    # is quadratic, so this is the kernel's case to win (round-2 review
    # weak #3: the kernel existed but nothing measured it; the default
    # stays "xla" unless this leg shows a win). Fully fenced.
    flash_xla_tok_s = flash_pl_tok_s = None
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            FLASH_LEN = 1024
            long_tokens = jnp.asarray(
                [[cfg.bos_token_id] + [7] * (FLASH_LEN - 1)], jnp.int32
            )
            fplen = jnp.int32(FLASH_LEN)

            def time_prefill(c):
                # K chained prefills, one fetch
                KF = 4

                def run():
                    ff = None
                    for _ in range(KF):
                        cf = M.init_kv_cache(c, 1, max_seq=FLASH_LEN + 8)
                        ff, _, cf = G.prefill(
                            c, params, long_tokens, fplen, cf, kp, sampling
                        )
                    fetch(ff)

                run()  # warm/compile
                t = max(
                    min(_timed(run)[0] for _ in range(3)) / KF, 1e-9
                )
                return FLASH_LEN / t

            flash_xla_tok_s = time_prefill(cfg)
            result["prefill_xla_1k_tok_s"] = round(flash_xla_tok_s, 1)
            flash_pl_tok_s = time_prefill(cfg.replace(attn_impl="pallas"))
            result["prefill_flash_1k_tok_s"] = round(flash_pl_tok_s, 1)
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # fleet-attention leg: the per-row flash decode kernel
    # (ops/paged_attention.flash_attend_slots) vs the XLA einsum over an
    # 8-slot 8k-window fleet cache at position ~1k — the
    # over-provisioned-window case the kernel targets. Driven DIRECTLY
    # (the serving hook always takes the XLA path for T=1 decode, where
    # the einsum measured decisively faster); this leg is the regression
    # baseline future kernel work has to beat. Fully fenced.
    fleet_xla_ms = fleet_pl_ms = None
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            from distributed_llm_inference_tpu.ops.attention import (
                attend, slot_causal_mask,
            )
            from distributed_llm_inference_tpu.ops.paged_attention import (
                flash_attend_slots,
            )

            FB, FS, FPOS = 8, 8192, 1024
            fk = jax.random.split(jax.random.PRNGKey(5), 3)
            fq = jax.random.normal(
                fk[0], (FB, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16
            )
            fck = jax.random.normal(
                fk[1], (FB, cfg.n_kv_heads, FS, cfg.head_dim), jnp.bfloat16
            )
            fcv = jax.random.normal(
                fk[2], (FB, cfg.n_kv_heads, FS, cfg.head_dim), jnp.bfloat16
            )
            fpos = jnp.full((FB,), FPOS, jnp.int32)
            fmask = slot_causal_mask(fpos, 1, FS)

            # operands are ARGUMENTS, not closure constants — a nullary
            # jit constant-folds the whole computation into the
            # executable and times nothing but the fetch
            att_x = jax.jit(attend)
            att_p = jax.jit(
                lambda q_, k_, v_, p_: flash_attend_slots(q_, k_, v_, p_)
            )

            def time_attn(fn, *args, n=20):
                fetch(fn(*args))  # warm/compile + drain
                t0 = time.perf_counter()
                for _ in range(n):
                    o = fn(*args)
                fetch(o)
                return max(time.perf_counter() - t0, 1e-9) / n * 1e3

            fleet_xla_ms = time_attn(att_x, fq, fck, fcv, fmask)
            result["fleet_attn_xla_ms"] = round(fleet_xla_ms, 3)
            fleet_pl_ms = time_attn(att_p, fq, fck, fcv, fpos)
            result["fleet_attn_flash_ms"] = round(fleet_pl_ms, 3)
            del fck, fcv
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # continuous-batching legs (engine/continuous.py): closed-loop client
    # fleet against the real serving engine — slot recycling, mid-flight
    # admission, lag-1 chunk pipelining — measured THREE ways (round-3
    # review #7: the serving-level features get round-over-round driver
    # numbers): dense fleet, block-paged pool, paged+prefix-reuse.
    # Reported as a nested result["continuous"] block.
    #
    cont_block = {}
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            from distributed_llm_inference_tpu.config import EngineConfig
            from distributed_llm_inference_tpu.engine.continuous import (
                ContinuousEngine,
            )
            from distributed_llm_inference_tpu.engine.engine import (
                InferenceEngine,
            )

            c_cfg, c_params = cfg, params
            kw = dict(max_tokens=32, greedy=True, chat=False)
            n_req, n_words, n_clients, n_slots, chunk = 16, 96, 8, 8, 16
            slot_max_seq = 1024
            blocks_per_slot = slot_max_seq // 32
            pool_blocks = n_slots * blocks_per_slot + blocks_per_slot + 1
            cont_block["model"] = c_cfg.name
            cont_block["platform"] = platform
            prompts = [
                " ".join(f"w{i}_{j}" for j in range(n_words))
                for i in range(n_req)
            ]
            # prefix-reuse mix: requests sharing one long prefix, so a
            # warm prefix snapshot serves every admission's prefill tail
            shared = " ".join(f"ctx{j}" for j in range(n_words + 32))
            prefix_prompts = [f"{shared} q{i}" for i in range(n_req)]

            def churn(cont, plist):
                cont.submit(plist[0], **kw)  # warm slot programs
                # warm the prefix-REUSE path too: the second serve of the
                # same prompt compiles the hit-side programs (block-map
                # gather + tail prefill-at-offset) so the timed window
                # measures steady state, same discipline as every other
                # leg's warmup (a no-op extra request when reuse is off)
                cont.submit(plist[0], **kw)
                done_tokens = [0]
                lock = threading.Lock()
                it = iter(plist)

                def client():
                    while True:
                        with lock:
                            p = next(it, None)
                        if p is None:
                            return
                        r = cont.submit(p, **kw)
                        if r.get("status") == "success":
                            with lock:
                                done_tokens[0] += r["tokens_generated"]

                t0 = time.perf_counter()
                threads = [
                    threading.Thread(target=client) for _ in range(8)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                return (done_tokens[0] / wall) if done_tokens[0] else None

            from distributed_llm_inference_tpu.utils.metrics import (
                latency_summary,
            )

            eng = InferenceEngine(c_cfg, params=c_params)
            # slot_max_seq on every leg: the tiny engine's default slot
            # capacity (128) is smaller than a byte-tokenized 32-word
            # prompt, which made the whole CPU dense leg reject requests
            cont = ContinuousEngine(
                eng, n_slots=n_slots, chunk_steps=chunk,
                slot_max_seq=slot_max_seq,
            )
            try:
                v = churn(cont, prompts)
                if v:
                    cont_block["dense_tokens_per_sec"] = round(v, 3)
                    # registry snapshot of the dense leg: TTFT/TPOT/step
                    # percentiles + occupancy, so BENCH_*.json rounds
                    # carry the stage-level signal, not just tok/s
                    cont_block["metrics"] = latency_summary(eng.metrics)
            finally:
                cont.close()

            # paged pool: same churn, fleet HBM now a function of
            # in-flight tokens (pool), admission backpressure on blocks.
            # slot budget slot_max_seq tokens (byte-tokenized prompts run
            # well under it) in blocks of 32; pool sized one spare
            # slot-class above the fleet. Each leg re-checks the deadline
            # like every other optional leg — the one before it may have
            # eaten the budget.
            if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
                cont = ContinuousEngine(
                    eng, n_slots=n_slots, chunk_steps=chunk,
                    slot_max_seq=slot_max_seq,
                    kv_pool_blocks=pool_blocks, kv_block_size=32,
                )
                try:
                    v = churn(cont, prompts)
                    if v:
                        cont_block["paged_tokens_per_sec"] = round(v, 3)
                        cont_block["paged"] = cont.stats().get("paged")
                finally:
                    cont.close()

            # paged + prefix reuse over the BUCKETED fallback
            # (ragged_prefill=False): admissions after the first MAP the
            # shared-prefix blocks straight into their tables (refcounted
            # block sharing, engine/block_prefix.py) and prefill only the
            # tail through the scratch gather + bucket ladder + insert
            # scatter — the baseline the ragged leg below is measured
            # against
            if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
                eng_px = InferenceEngine(
                    c_cfg, params=c_params,
                    engine_cfg=EngineConfig(
                        prefix_cache_entries=4, ragged_prefill=False
                    ),
                )
                cont = ContinuousEngine(
                    eng_px, n_slots=n_slots, chunk_steps=chunk,
                    slot_max_seq=slot_max_seq,
                    kv_pool_blocks=pool_blocks, kv_block_size=32,
                )
                try:
                    v = churn(cont, prefix_prompts)
                    if v:
                        cont_block["paged_prefix_tokens_per_sec"] = round(v, 3)
                        # the round-over-round cliff tracker: shared-prompt
                        # churn relative to the plain paged leg (was ~0.13x
                        # under snapshot-splice-scatter in BENCH_r05)
                        base = cont_block.get("paged_tokens_per_sec")
                        if base:
                            cont_block["paged_prefix_speedup"] = round(
                                v / base, 3
                            )
                        st = cont.stats()
                        cont_block["prefix_cache"] = st.get("prefix_cache")
                        cont_block["paged_sharing"] = st.get("paged")
                finally:
                    cont.close()

            # ragged leg: the SAME mixed prefill+decode shared-prefix
            # churn through the ragged ingest (engine_cfg.ragged_prefill
            # default-on — admission prefills straight into the pool, one
            # compiled launch pair for any tail, exact-depth prefix
            # reuse). Reported side by side with the bucketed
            # paged_prefix leg so the BENCH trajectory captures the gap
            # closing (~50 tok/s in r05).
            if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
                eng_rg = InferenceEngine(
                    c_cfg, params=c_params,
                    # chunked_prefill=False: this leg tracks the
                    # PER-ADMISSION ragged ingest vs the bucketed
                    # fallback (round-over-round comparability with
                    # BENCH_r05); the chunked scheduler has its own
                    # sched_interleave leg below
                    engine_cfg=EngineConfig(
                        prefix_cache_entries=4, chunked_prefill=False
                    ),
                )
                cont = ContinuousEngine(
                    eng_rg, n_slots=n_slots, chunk_steps=chunk,
                    slot_max_seq=slot_max_seq,
                    kv_pool_blocks=pool_blocks, kv_block_size=32,
                )
                try:
                    v = churn(cont, prefix_prompts)
                    if v:
                        cont_block["ragged_tokens_per_sec"] = round(v, 3)
                        base = cont_block.get("paged_prefix_tokens_per_sec")
                        if base:
                            cont_block["ragged_vs_prefix_speedup"] = round(
                                v / base, 3
                            )
                        st = cont.stats()
                        cont_block["ragged_paged"] = st.get("paged")
                        snap = eng_rg.metrics.snapshot()

                        def _ctr(name):
                            return {
                                "|".join(
                                    f"{k}={v2}"
                                    for k, v2 in sorted(
                                        s["labels"].items()
                                    )
                                ) or "_": s["value"]
                                for s in snap.get(name, {}).get(
                                    "series", []
                                )
                            }

                        cont_block["ragged_metrics"] = {
                            "rows": _ctr("dli_ragged_rows_total"),
                            "tiles": _ctr("dli_ragged_tiles_total"),
                            "launches": _ctr("dli_ragged_launches_total"),
                            "exact_prefix_hits": _ctr(
                                "dli_ragged_exact_prefix_hits_total"
                            ),
                            "compiled_programs": _ctr(
                                "dli_ragged_compiled_programs"
                            ),
                        }
                finally:
                    cont.close()

            # SLO-aware chunked-prefill scheduler leg (engine/
            # scheduler.py): LONG prompts keep arriving while a request
            # streams steady decode. Whole-prefill admission stalls every
            # decoding request for each full prefill; the chunked
            # scheduler slices the prompt into budget-sized chunks
            # interleaved with the decode rows in ONE mixed launch per
            # step. Decode TPOT p99 is the standard inter-token-latency
            # percentile over the streamed token arrivals (a k-token
            # burst = one gap + k-1 zeros — tokens arriving together
            # cost the client one wait), so the whole-prefill stall
            # lands on the token that actually waited out the prefill.
            # Reported: sched_interleave_tpot_p99 vs
            # whole_prefill_tpot_p99 + ratio and the worst single stall.
            # (CPU proxy caveat: compute here is width-linear, so the
            # interleave win is structurally understated vs a TPU, where
            # small-batch launches are latency-bound and overlapping
            # prefill compute under decode is nearly free.)
            if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
                long_prompt = "d " * int(slot_max_seq * 0.43)
                sched_budget = n_slots * 8 + 8

                def interleave_leg(chunked):
                    eng_i = InferenceEngine(
                        c_cfg, params=c_params,
                        engine_cfg=EngineConfig(
                            prefix_cache_entries=0,
                            chunked_prefill=chunked,
                            step_token_budget=sched_budget,
                        ),
                    )
                    cont = ContinuousEngine(
                        eng_i, n_slots=n_slots, chunk_steps=chunk,
                        chunk_lag=1, slot_max_seq=slot_max_seq,
                        kv_pool_blocks=pool_blocks, kv_block_size=32,
                    )
                    itl, toks = [], [0]
                    lock = threading.Lock()
                    stop = threading.Event()
                    try:
                        cont.submit(prompts[0], **dict(kw, max_tokens=40))
                        cont.submit(long_prompt, **dict(kw, max_tokens=2))

                        def decoder():
                            last_t, last_n = None, 0
                            for ev in cont.stream(
                                "steady decoder",
                                **dict(kw, max_tokens=150),
                            ):
                                now = time.perf_counter()
                                if ev.get("done"):
                                    break
                                n = ev.get("tokens_so_far", last_n)
                                dn = n - last_n
                                if last_t is not None and dn > 0:
                                    with lock:
                                        itl.append(now - last_t)
                                        itl.extend([0.0] * (dn - 1))
                                last_t, last_n = now, n
                            stop.set()

                        def longs():
                            while not stop.is_set():
                                r = cont.submit(
                                    long_prompt, **dict(kw, max_tokens=2)
                                )
                                if r.get("status") == "success":
                                    with lock:
                                        toks[0] += (
                                            r["tokens_generated"]
                                            + r["prompt_tokens"]
                                        )
                                time.sleep(0.06)

                        t0 = time.perf_counter()
                        ts = [threading.Thread(target=decoder)] + [
                            threading.Thread(target=longs)
                            for _ in range(2)
                        ]
                        for t in ts:
                            t.start()
                        for t in ts:
                            t.join()
                        wall = time.perf_counter() - t0
                    finally:
                        cont.close()
                    if not itl:
                        return None
                    itl.sort()
                    return {
                        "tpot_p99_s": round(
                            itl[min(len(itl) - 1, int(0.99 * len(itl)))], 5
                        ),
                        "max_stall_s": round(itl[-1], 5),
                        "tokens_per_sec": round((toks[0] + 150) / wall, 3),
                        "itl_samples": len(itl),
                    }

                sched_leg = interleave_leg(True)
                whole_leg = interleave_leg(False)
                if sched_leg and whole_leg:
                    cont_block["sched_interleave_tpot_p99"] = sched_leg[
                        "tpot_p99_s"
                    ]
                    cont_block["whole_prefill_tpot_p99"] = whole_leg[
                        "tpot_p99_s"
                    ]
                    if sched_leg["tpot_p99_s"] > 0:
                        cont_block["sched_tpot_p99_improvement"] = round(
                            whole_leg["tpot_p99_s"]
                            / sched_leg["tpot_p99_s"], 3,
                        )
                    cont_block["sched_interleave"] = {
                        "chunked": sched_leg, "whole_prefill": whole_leg,
                        "step_token_budget": sched_budget,
                        "long_prompt_tokens_approx": int(
                            slot_max_seq * 0.86
                        ),
                    }
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # recovery leg (engine/shadow.py warm-state recovery): the same
    # long-prompt request served across a mid-decode scheduler crash,
    # shadow ON (warm: restore + partial-tail re-prefill) vs OFF (cold:
    # whole-prompt re-prefill). time_to_recover = faulted wall minus the
    # fault-free wall of the identical request, so the number isolates
    # the recovery cost; tokens_recomputed comes straight off
    # dli_recovery_tokens_recomputed_total. Headline:
    # warm_recovery_speedup = cold time-to-recover / warm.
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            from distributed_llm_inference_tpu.utils import faults as _faults

            long_p = "r " * int(slot_max_seq * 0.4)

            def _ctr_total(eng_x, name):
                snap = eng_x.metrics.snapshot()
                return sum(
                    s["value"]
                    for s in snap.get(name, {}).get("series", [])
                )

            def recovery_leg(warm):
                eng_v = InferenceEngine(
                    c_cfg, params=c_params,
                    engine_cfg=EngineConfig(prefix_cache_entries=4),
                )
                cont = ContinuousEngine(
                    eng_v, n_slots=n_slots, chunk_steps=chunk,
                    slot_max_seq=slot_max_seq,
                    kv_pool_blocks=pool_blocks, kv_block_size=32,
                    restart_backoff_s=0.01, kv_shadow=warm,
                )
                try:
                    cont.submit(long_p, **kw)  # compile + shadow warm
                    # warm the RECOVERY path too (the whole-prefill
                    # re-admission programs the chunked serving path
                    # never compiles, plus the restore scatter) with a
                    # throwaway crash — the timed window below measures
                    # steady-state recovery, not jit latency, same
                    # discipline as every other leg's warmup
                    _faults.arm([_faults.FaultRule(
                        "decode_launch", "transient", on_call=2
                    )])
                    cont.submit(long_p, **kw)
                    _faults.disarm()
                    t0 = time.perf_counter()
                    r_clean = cont.submit(long_p, **kw)
                    clean_s = time.perf_counter() - t0
                    if warm:
                        cont._shadow.flush(10.0)
                    base = _ctr_total(
                        eng_v, "dli_recovery_tokens_recomputed_total"
                    )
                    _faults.arm([_faults.FaultRule(
                        "decode_launch", "transient", on_call=3
                    )])
                    t0 = time.perf_counter()
                    r_fault = cont.submit(long_p, **kw)
                    fault_s = time.perf_counter() - t0
                    _faults.disarm()
                    ok = (
                        r_fault.get("status") == "success"
                        and r_fault.get("response")
                        == r_clean.get("response")
                        and cont.restarts_total == 2
                    )
                    return {
                        "ok": ok,
                        "clean_request_s": round(clean_s, 4),
                        "faulted_request_s": round(fault_s, 4),
                        "time_to_recover_s": round(
                            max(0.0, fault_s - clean_s), 4
                        ),
                        "tokens_recomputed": int(_ctr_total(
                            eng_v, "dli_recovery_tokens_recomputed_total"
                        ) - base),
                        "restored_blocks": cont.shadow_restored_total,
                    }
                finally:
                    _faults.disarm()
                    cont.close()

            warm_leg = recovery_leg(True)
            cold_leg = recovery_leg(False)
            cont_block["recovery"] = {
                "warm": warm_leg, "cold": cold_leg,
                "prompt_tokens_approx": len(long_p),
                "kv_block_size": 32,
            }
            if (
                warm_leg["ok"] and cold_leg["ok"]
                and warm_leg["time_to_recover_s"] > 0
            ):
                cont_block["warm_recovery_speedup"] = round(
                    cold_leg["time_to_recover_s"]
                    / warm_leg["time_to_recover_s"], 3,
                )
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # overload leg (SLO-aware KV preemption, engine/continuous.py
    # _preempt_for): a low-priority HOG decode holds most of a pool
    # sized to ~60% of the combined working set while deadline-carrying
    # interactive requests arrive. Shed-only ("off"): each interactive
    # admission waits for the hog's full decode and blows its
    # deadline_ms (504). Preemption ("swap"): the hog is evicted
    # (lowest weight), its KV swapped to the host shadow, and the
    # interactive stream completes inside its deadlines; the hog
    # resumes between arrivals. Headline: interactive completion rate
    # + p99 — "pool full" as a policy decision, not a tail-latency
    # cliff.
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            ov_bs = 32
            ov_slot_seq = 512  # 16 blocks
            hog_p = prompts[0]
            # the hog's budget fills its WHOLE slot class, so its blocks
            # span the entire usable pool and no short can be placed
            # beside it — the pool lands at ~60% of the combined
            # (hog + interactive stream) working set
            hog_mt = ov_slot_seq - (len(hog_p) + 8) - 1
            hog_kw = dict(max_tokens=hog_mt, greedy=True, chat=False,
                          slo_class="batch")
            short_p = "interactive q"
            short_kw = dict(max_tokens=8, greedy=True, chat=False,
                            slo_class="interactive")
            hog_need = -(-(len(hog_p) + 8 + hog_mt) // ov_bs)
            short_need = -(-(len(short_p) + 8 + 8) // ov_bs)
            ov_pool = ov_slot_seq // ov_bs + 1  # usable == one slot class
            n_short = 6

            def overload_leg(policy):
                eng_o = InferenceEngine(
                    c_cfg, params=c_params,
                    engine_cfg=EngineConfig(
                        prefix_cache_entries=4, preempt_policy=policy,
                        # the livelock cap exists for safety; the bench
                        # measures the policy ceiling, so let the hog be
                        # preempted once per interactive arrival
                        max_preemptions_per_req=64,
                    ),
                )
                cont = ContinuousEngine(
                    eng_o, n_slots=n_slots, chunk_steps=chunk,
                    slot_max_seq=ov_slot_seq,
                    kv_pool_blocks=ov_pool, kv_block_size=ov_bs,
                )
                try:
                    cont.submit(hog_p, **dict(hog_kw, max_tokens=8))
                    t0 = time.perf_counter()
                    clean = cont.submit(short_p, **short_kw)
                    clean_s = time.perf_counter() - t0
                    if clean.get("status") != "success":
                        return None
                    deadline_ms = max(200.0, 6 * clean_s * 1e3)
                    hog_out = {}

                    def run_hog():
                        hog_out["r"] = cont.submit(hog_p, **hog_kw)

                    th = threading.Thread(target=run_hog)
                    th.start()
                    while cont.stats()["occupied"] < 1:
                        time.sleep(0.002)
                    walls, ok = [], 0
                    t0 = time.perf_counter()
                    for _ in range(n_short):
                        t1 = time.perf_counter()
                        r = cont.submit(
                            short_p, deadline_ms=deadline_ms, **short_kw
                        )
                        w = time.perf_counter() - t1
                        if r.get("status") == "success":
                            ok += 1
                            walls.append(w)
                        time.sleep(0.01)
                    wall = time.perf_counter() - t0
                    th.join(timeout=120)
                    walls.sort()
                    return {
                        "offered": n_short,
                        "completed": ok,
                        "completion_rate": round(ok / n_short, 3),
                        "p99_s": round(
                            walls[min(len(walls) - 1,
                                      int(0.99 * len(walls)))], 4,
                        ) if walls else None,
                        "deadline_ms": round(deadline_ms, 1),
                        "wall_s": round(wall, 3),
                        "preempted": cont.preempted_total,
                        "hog_status": hog_out.get("r", {}).get("status"),
                    }
                finally:
                    cont.close()

            preempt_leg = overload_leg("swap")
            shed_leg = overload_leg("off")
            if preempt_leg and shed_leg:
                cont_block["overload"] = {
                    "preempt": preempt_leg, "shed_only": shed_leg,
                    "pool_blocks": ov_pool,
                    "working_set_blocks": hog_need + 6 * short_need,
                }
                cont_block["overload_completion_rate"] = preempt_leg[
                    "completion_rate"
                ]
                cont_block["overload_completion_rate_shed_only"] = shed_leg[
                    "completion_rate"
                ]
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # multi-tenant adapter-serving leg (ISSUE 16: engine/adapters.py
    # paged runtime LoRA): one resident base + a refcounted LRU page
    # pool serving three registered adapters, driven by a mixed client
    # fleet where every request carries (adapter, tenant) — base rows
    # and two adapters interleave inside the SAME compiled mixed
    # launches. Measured against the naive alternative the subsystem
    # replaces: serving each adapter's traffic as its own sequential
    # fleet (what merge-at-load forces — one merged model resident at a
    # time). Headlines: mixed_tokens_per_sec vs adapter-sequential
    # tok/s + the consolidation speedup; a mixed-vs-solo greedy
    # identity probe (the same prompt+adapter must emit the same text
    # inside the mix as alone); per-tenant completed-token spread
    # (fairness under the weighted scheduler split); and the pool
    # ledger after an eviction probe (3 adapters through 2 pages ->
    # swaps > 0, referenced == 0 after drain).
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            import numpy as _np

            from distributed_llm_inference_tpu.engine.adapters import (
                adapter_leaf_dims,
                attach_adapter_pool,
            )

            mt_rank = 4
            mt_ads = ["ad-a", "ad-b", "ad-c"]

            def _mt_adapter(seed):
                rng = _np.random.default_rng(seed)
                L = c_cfg.n_layers
                return {
                    leaf: (
                        (rng.standard_normal((L, d_in, mt_rank))
                         * 0.02).astype(_np.float32),
                        (rng.standard_normal((L, mt_rank, d_out))
                         * 0.02).astype(_np.float32),
                    )
                    for leaf, (d_in, d_out)
                    in adapter_leaf_dims(c_cfg).items()
                }

            eng_mt = InferenceEngine(
                c_cfg, params=c_params,
                engine_cfg=EngineConfig(
                    prefix_cache_entries=0,
                    tenant_weights=(("acme", 1.0), ("globex", 1.0)),
                ),
            )
            pool_mt = attach_adapter_pool(eng_mt, slots=2, rank=mt_rank)
            for i, nm in enumerate(mt_ads):
                pool_mt.register(nm, _mt_adapter(11 + i))
            cont = ContinuousEngine(
                eng_mt, n_slots=n_slots, chunk_steps=chunk,
                slot_max_seq=slot_max_seq,
                kv_pool_blocks=pool_blocks, kv_block_size=32,
            )
            try:
                # warm the base and adapter paths (same program — the
                # pages operand is traced — but the first adapter
                # admission pays the page write)
                cont.submit(prompts[0], **kw)
                cont.submit(prompts[0], adapter="ad-a", **kw)
                # identity probe reference: prompt[1] under ad-a, alone
                solo_ref = cont.submit(prompts[1], adapter="ad-a", **kw)

                def mt_churn(jobs):
                    """jobs: [(prompt, adapter|None, tenant|None)].
                    Returns (tok/s, per-tenant tokens, outputs)."""
                    done = [0]
                    per_tenant: dict = {}
                    outs: dict = {}
                    lock = threading.Lock()
                    it = iter(jobs)

                    def client():
                        while True:
                            with lock:
                                j = next(it, None)
                            if j is None:
                                return
                            p, ad, ten = j
                            extra = {}
                            if ad:
                                extra["adapter"] = ad
                            if ten:
                                extra["tenant"] = ten
                            r = cont.submit(p, **kw, **extra)
                            if r.get("status") == "success":
                                with lock:
                                    done[0] += r["tokens_generated"]
                                    key = ten or ""
                                    per_tenant[key] = (
                                        per_tenant.get(key, 0)
                                        + r["tokens_generated"]
                                    )
                                    outs[(p, ad)] = r.get("response")

                    t0 = time.perf_counter()
                    threads = [
                        threading.Thread(target=client) for _ in range(8)
                    ]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    wall = time.perf_counter() - t0
                    tps = (done[0] / wall) if done[0] else None
                    return tps, per_tenant, outs, wall

                mixed_jobs = [
                    (
                        prompts[i % n_req],
                        (None, "ad-a", "ad-b")[i % 3],
                        ("acme", "globex")[i % 2],
                    )
                    for i in range(n_req * 2)
                ]
                mixed_tps, per_tenant, outs, _ = mt_churn(mixed_jobs)

                # the consolidation baseline: the same jobs grouped by
                # adapter and served as three back-to-back fleets (the
                # merge-at-load world — one adapter resident at a time)
                solo_tokens, solo_wall = 0, 0.0
                for ad in (None, "ad-a", "ad-b"):
                    group = [j for j in mixed_jobs if j[1] == ad]
                    tps_g, pt_g, _, wall_g = mt_churn(group)
                    solo_tokens += sum(pt_g.values())
                    solo_wall += wall_g
                solo_tps = (
                    solo_tokens / solo_wall if solo_tokens else None
                )

                # eviction probe: ad-c through the 2-page pool evicts
                # the LRU resident (a swap) — referenced pages stay
                # untouchable, and after the drain nothing holds a page
                cont.submit(prompts[2], adapter="ad-c", **kw)

                mt_block = {
                    "adapters": len(mt_ads),
                    "pool_pages": pool_mt.total,
                    "rank": mt_rank,
                    # CPU proxy caveat: compute here is width-linear, so
                    # co-batching adapter mixes buys no launch
                    # amortization — the consolidation win is
                    # structurally understated vs a TPU, where the
                    # sequential baseline pays one weight stream PER
                    # fleet while the mix pays one total
                    "note": (
                        "consolidation_speedup is launch-amortization "
                        "bound; CPU proxy understates it"
                    ) if platform != "tpu" else None,
                    "mixed_tokens_per_sec": (
                        round(mixed_tps, 3) if mixed_tps else None
                    ),
                    "adapter_sequential_tokens_per_sec": (
                        round(solo_tps, 3) if solo_tps else None
                    ),
                    "mixed_matches_solo": (
                        outs.get((prompts[1], "ad-a"))
                        == solo_ref.get("response")
                    ),
                    "tenant_tokens": dict(sorted(per_tenant.items())),
                    "pool": pool_mt.stats(),
                    "referenced_after_drain": pool_mt.referenced(),
                }
                if mixed_tps and solo_tps:
                    mt_block["consolidation_speedup"] = round(
                        mixed_tps / solo_tps, 3
                    )
                vals = [v for k, v in per_tenant.items() if k]
                if len(vals) >= 2 and max(vals) > 0:
                    mt_block["tenant_fairness_min_over_max"] = round(
                        min(vals) / max(vals), 3
                    )
                cont_block["multi_tenant"] = mt_block
                if mixed_tps:
                    cont_block["mixed_adapter_tokens_per_sec"] = round(
                        mixed_tps, 3
                    )
            finally:
                cont.close()
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # speculative-decoding leg (ISSUE 13: draft-then-verify inside the
    # mixed launch, engine/paged.spec_verify + the scheduler's n-gram
    # planner): drive the REAL compiled mixed program launch for launch,
    # plain 1-token decode rows vs [current + K-draft] verify rows, on a
    # self-repeating stream (drafts accept) and with forced-junk drafts
    # (the rejection worst case — a verify row occupies the same query
    # tile as a plain row, so rejection must cost ~nothing). Headlines:
    # accepted_tokens_per_launch, per-token TPOT p50/p99 per variant,
    # spec_tpot_speedup = plain p99 / spec p99. Launch-normalized on
    # purpose: each launch streams the full weights on a TPU, so
    # tokens-per-launch IS the decode-speed lever; the CPU proxy's
    # width-linear attention understates nothing at this granularity
    # because both variants time the IDENTICAL compiled program.
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            import numpy as _np

            from distributed_llm_inference_tpu.engine import generate as _G
            from distributed_llm_inference_tpu.engine import paged as _EP
            from distributed_llm_inference_tpu.engine.scheduler import (
                ngram_draft,
            )

            sp_bs, sp_MB, sp_W, sp_K = 32, 16, 32, 4
            K1 = sp_K + 1
            sp_table = jnp.asarray([list(range(1, sp_MB + 1))], jnp.int32)
            sp_arm = _EP.idle_mixed_arm(1, c_cfg.vocab_size)
            sp_key = jax.random.PRNGKey(5)
            spec_tokens_target = 64 if platform != "tpu" else 128
            # two prompts, prefilled into the pool (three real ragged
            # extends each) so the drafts verify against real KV: a
            # periodic one — the "repetitive/structured" workload the
            # speculation targets — and a unique-token one, the
            # incompressible leg (the n-gram planner finds no draft →
            # plain decode rows → the machinery must cost nothing)
            sp_ids_rep = ([100, 101, 35] * 33)[:97]
            sp_ids_unique = [
                (40 + 7 * j) % c_cfg.vocab_size for j in range(97)
            ]

            def spec_program_leg(mode, sp_ids):
                """mode: 'plain' | 'ngram' | 'junk'. Returns per-token
                TPOT samples + tokens/launch over a timed window."""
                pool = _EP.init_pool(c_cfg, sp_MB + 2, sp_bs)
                for c in range(3):
                    meta, tok_row, tok_pos, _, _ = _EP.build_ragged_meta(
                        [(0, c * 32, 32, _EP.RAGGED_PREFILL)],
                        width=sp_W, tile=8,
                    )
                    pool = _EP.extend_ragged_paged(
                        c_cfg, c_params,
                        jnp.asarray(sp_ids[c * 32 : (c + 1) * 32],
                                    jnp.int32),
                        jnp.asarray(tok_row), jnp.asarray(tok_pos),
                        jnp.asarray(meta), pool, sp_table,
                    )
                state, sparams = _G.init_slots(1, c_cfg.vocab_size)
                hist = list(sp_ids)
                state = state._replace(
                    token=jnp.asarray([hist[-1]], jnp.int32),
                    pos=jnp.asarray([len(hist) - 1], jnp.int32),
                    active=jnp.asarray([True]),
                    remaining=jnp.asarray([4096], jnp.int32),
                )
                sparams = sparams._replace(greedy=jnp.asarray([True]))
                samples, launches, emitted_total = [], 0, 0
                wall_samples = []  # per-token wall clock (wall / tokens)
                warm_until = 64

                def one_launch(state, pool):
                    pos_h = len(hist) - 1
                    draft = []
                    if mode == "ngram":
                        draft = ngram_draft(hist, sp_K)
                    elif mode == "junk":
                        draft = [
                            (13 + 7 * (pos_h + j)) % c_cfg.vocab_size
                            for j in range(sp_K)
                        ]
                    n_d = len(draft)
                    kind = (
                        _EP.RAGGED_PREFILL if n_d else _EP.RAGGED_DECODE
                    )
                    meta, tok_row, tok_pos, offs, _ = (
                        _EP.build_ragged_meta(
                            [(0, pos_h, 1 + n_d, kind)],
                            width=sp_W, tile=8,
                        )
                    )
                    toks = _np.zeros((sp_W,), _np.int32)
                    dec_flag = _np.zeros((sp_W,), bool)
                    dec_flag[offs[0]] = True
                    spec = None
                    if n_d:
                        toks[offs[0] + 1 : offs[0] + 1 + n_d] = draft
                        idxs = offs[0] + _np.arange(K1, dtype=_np.int32)
                        idxs[n_d + 1:] = offs[0] + n_d
                        spec = _EP.SpecPlan(
                            jnp.asarray([False]), jnp.asarray([True]),
                            jnp.asarray(idxs[None, :]),
                            jnp.asarray([n_d], jnp.int32),
                        )
                    return _EP.mixed_step_ragged(
                        c_cfg, c_params, jnp.asarray(toks),
                        jnp.asarray(tok_row), jnp.asarray(tok_pos),
                        jnp.asarray(dec_flag), jnp.asarray(meta), pool,
                        sp_table, state, sparams, sp_key,
                        jnp.asarray([offs[0] if not n_d else 0],
                                    jnp.int32),
                        sp_arm, spec=spec,
                    ), n_d

                while emitted_total < warm_until + spec_tokens_target:
                    t0 = time.perf_counter()
                    (packed, state, sparams, pool), n_d = one_launch(
                        state, pool
                    )
                    p = _np.asarray(packed)  # the fetch
                    wall = time.perf_counter() - t0
                    if n_d:
                        em = p[5 : 5 + K1, 0]
                        mk = p[5 + K1 : 5 + 2 * K1, 0].astype(bool)
                        got = em[mk].tolist()
                    else:
                        got = [int(p[0, 0])] if p[1, 0] else []
                    if not got:
                        break  # stop token: restart would skew timing
                    hist.extend(int(t) for t in got)
                    emitted_total += len(got)
                    if emitted_total > warm_until:
                        launches += 1
                        samples.append(wall)
                        samples.extend([0.0] * (len(got) - 1))
                        wall_samples.extend(
                            [wall / len(got)] * len(got)
                        )
                if not samples:
                    return None
                s = sorted(samples)
                w = sorted(wall_samples)
                return {
                    "tokens": len(samples),
                    "launches": launches,
                    "tokens_per_launch": round(
                        len(samples) / launches, 3
                    ),
                    "tpot_p50_s": round(s[len(s) // 2], 6),
                    "tpot_p99_s": round(
                        s[min(len(s) - 1, int(0.99 * len(s)))], 6
                    ),
                    "tpot_mean_s": round(sum(s) / len(s), 6),
                    # wall-clock per-token percentiles (each launch's
                    # wall amortized over its emitted tokens): the
                    # cross-leg-comparable TPOT trajectory — the ITL
                    # samples above pin whole launch walls to single
                    # tokens by design, so their p50/p99 are not
                    # comparable to the serving legs' TPOT numbers
                    "wall_tpot_p50_s": round(w[len(w) // 2], 6),
                    "wall_tpot_p99_s": round(
                        w[min(len(w) - 1, int(0.99 * len(w)))], 6
                    ),
                    "wall_tpot_mean_s": round(sum(w) / len(w), 6),
                }

            plain_leg = spec_program_leg("plain", sp_ids_rep)
            ngram_leg = spec_program_leg("ngram", sp_ids_rep)
            plain_u = spec_program_leg("plain", sp_ids_unique)
            ngram_u = spec_program_leg("ngram", sp_ids_unique)
            junk_leg = spec_program_leg("junk", sp_ids_rep)
            if plain_leg and ngram_leg:
                spec_block = {
                    "plain": plain_leg,
                    "speculative": ngram_leg,
                    "incompressible_plain": plain_u,
                    "incompressible_spec": ngram_u,
                    "rejected_drafts": junk_leg,
                    "draft_len": sp_K,
                    "launch_width": sp_W,
                }
                spec_block["accepted_tokens_per_launch"] = ngram_leg[
                    "tokens_per_launch"
                ]
                if ngram_leg["tpot_p99_s"] > 0:
                    spec_block["spec_tpot_speedup"] = round(
                        plain_leg["tpot_p99_s"] / ngram_leg["tpot_p99_s"],
                        3,
                    )
                if ngram_leg["tpot_mean_s"] > 0:
                    # mean TPOT is the steadier headline at this sample
                    # count: ITL-style accounting pins every p99 sample
                    # to a whole launch wall, so p99 can only show the
                    # per-launch delta, never the tokens-per-launch win
                    spec_block["spec_tpot_mean_speedup"] = round(
                        plain_leg["tpot_mean_s"]
                        / ngram_leg["tpot_mean_s"], 3,
                    )
                if (
                    plain_u and ngram_u and plain_u["tpot_p99_s"] > 0
                ):
                    # the production incompressible path: no bigram
                    # match → plain decode rows → ~1.0 (no regression)
                    spec_block["incompressible_tpot_ratio"] = round(
                        ngram_u["tpot_p99_s"] / plain_u["tpot_p99_s"], 3
                    )
                    if ngram_u["tpot_mean_s"] > 0:
                        spec_block["incompressible_tpot_mean_ratio"] = (
                            round(
                                ngram_u["tpot_mean_s"]
                                / plain_u["tpot_mean_s"], 3,
                            )
                        )
                if junk_leg and plain_leg["tpot_p99_s"] > 0:
                    # the FORCED worst case: every launch a verify row,
                    # every draft rejected — bounds the overhead of a
                    # verify row (same query tile as a plain row)
                    spec_block["rejected_tpot_ratio"] = round(
                        junk_leg["tpot_p99_s"] / plain_leg["tpot_p99_s"],
                        3,
                    )
                cont_block["speculative"] = spec_block
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # spec_lag leg (ISSUE 15: device-derived launch metadata): the REAL
    # serving loop — a 4-slot chunked fleet, 3 speculating greedy
    # streams plus one long-lived sampled (spec-ineligible) stream that
    # keeps the scheduler launching throughout — with the
    # skip-until-fetched freeze DELETED (spec_device_meta=True, verify
    # rows back-to-back under lag pipelining) vs the PR-13 baseline
    # (=False: a slot with an unfetched verify row carries no row, so
    # every launch that fires while it waits still streams the full
    # weights WITHOUT it). Speculation runs the draft-model flavor with
    # draft == target, so acceptance is real and equal on both paths
    # (the random-weight proxy's n-gram acceptance is ~0 — real weights
    # would supply it; the freeze cost being measured is identical
    # either way). Headlines: launches-per-accepted-token over the
    # speculating streams' LIFETIME (mixed launches fired until the
    # last one finished / their emitted tokens — LOWER is better; the
    # freeze structurally inflates it) and wall-clock TPOT p50/p99,
    # with greedy output asserted bit-identical across the two paths.
    # Gate: >= 1.3x launches-per-token improvement on this proxy.
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            lag_rep = "the cat sat on the mat " * 10
            lag_bg = " ".join(f"u{j}_{j * 7}" for j in range(24))
            lag_kw = dict(max_tokens=48, greedy=True, chat=False)

            def spec_lag_leg(device_meta):
                eng = InferenceEngine(
                    c_cfg, params=c_params,
                    engine_cfg=EngineConfig(
                        prefix_cache_entries=0, chunked_prefill=True,
                        step_token_budget=64,
                        prefill_buckets=(64, 128, 256),
                        spec_decode=True, spec_draft_len=4,
                        spec_draft_model=c_cfg.name,
                        spec_device_meta=device_meta,
                    ),
                )
                eng.set_draft(c_cfg, c_params)  # draft == target
                cont = ContinuousEngine(
                    eng, n_slots=4, chunk_steps=8,
                    slot_max_seq=slot_max_seq,
                    kv_pool_blocks=pool_blocks, kv_block_size=32,
                )
                try:
                    # warm every program (spec + plain + sampled)
                    cont.submit(lag_rep, max_tokens=8, greedy=True,
                                chat=False)
                    cont.submit(lag_bg, max_tokens=8, greedy=False,
                                temperature=0.9, chat=False)
                    fam = eng.metrics.get("dli_ragged_launches_total")

                    def mixed_launches():
                        return sum(
                            s["value"]
                            for s in fam.snapshot()["series"]
                            if s["labels"].get("phase") == "mixed"
                        )

                    base_launches = mixed_launches()
                    st0 = cont.stats().get("speculative", {})
                    out = [None] * 3
                    lock = threading.Lock()
                    marks = []
                    started = threading.Event()

                    def rep_client(i):
                        started.wait(30)
                        r = cont.submit(lag_rep, **lag_kw)
                        with lock:
                            marks.append(mixed_launches())
                        out[i] = r

                    def bg_client():
                        started.set()
                        cont.submit(lag_bg, max_tokens=200, greedy=False,
                                    temperature=0.9, chat=False)

                    t0 = time.perf_counter()
                    threads = [threading.Thread(target=bg_client)] + [
                        threading.Thread(target=rep_client, args=(i,))
                        for i in range(3)
                    ]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    wall = time.perf_counter() - t0
                    st = cont.stats().get("speculative", {})
                finally:
                    cont.close()
                if any(
                    r is None or r.get("status") != "success" for r in out
                ) or not marks:
                    return None
                launches = max(marks) - base_launches
                tokens = sum(r["tokens_generated"] for r in out)
                tpots = sorted(
                    max(0.0, float(str(r["time_taken"]).rstrip("s"))
                        - r["ttft_s"]) / (r["tokens_generated"] - 1)
                    for r in out if r["tokens_generated"] > 1
                )
                leg = {
                    "device_meta": device_meta,
                    "mixed_launches_in_window": int(launches),
                    "tokens": int(tokens),
                    "accepted_tokens": (
                        st.get("accepted_tokens", 0)
                        - st0.get("accepted_tokens", 0)
                    ),
                    "spec_launches": (
                        st.get("launches", 0) - st0.get("launches", 0)
                    ),
                    "pipelined_launches": st.get("pipelined_launches", 0),
                    "wall_s": round(wall, 4),
                }
                if tokens and launches:
                    leg["launches_per_token"] = round(
                        launches / tokens, 4
                    )
                if tpots:
                    leg["wall_tpot_p50_s"] = round(
                        tpots[len(tpots) // 2], 6
                    )
                    leg["wall_tpot_p99_s"] = round(tpots[-1], 6)
                return leg, sorted(r["response"] for r in out)

            lag_dev = spec_lag_leg(True)
            lag_base = spec_lag_leg(False)
            if lag_dev and lag_base:
                dev_leg, dev_out = lag_dev
                base_leg, base_out = lag_base
                lag_block = {
                    "device_meta": dev_leg,
                    "pr13_frozen_baseline": base_leg,
                    "draft_len": 4,
                    # the two paths are a launch strategy, never a
                    # semantics change
                    "bit_identical": dev_out == base_out,
                }
                if (
                    dev_leg.get("launches_per_token")
                    and base_leg.get("launches_per_token")
                ):
                    imp = (
                        base_leg["launches_per_token"]
                        / dev_leg["launches_per_token"]
                    )
                    lag_block["launches_per_token_improvement"] = round(
                        imp, 3
                    )
                    lag_block["gate_1p3x"] = bool(imp >= 1.3)
                if (
                    dev_leg.get("wall_tpot_p99_s")
                    and base_leg.get("wall_tpot_p99_s")
                ):
                    lag_block["wall_tpot_p99_speedup"] = round(
                        base_leg["wall_tpot_p99_s"]
                        / dev_leg["wall_tpot_p99_s"], 3,
                    )
                cont_block["spec_lag"] = lag_block
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # disagg leg (serving/kv_fabric.py + the router's prefill/decode
    # handoff): 1 prefill-class + 1 decode-class replica vs 2 mixed
    # replicas — REAL HTTP servers behind a real Router — under a
    # prefix-churn workload: a background stream of FRESH long prompts
    # (pure prefill load) while a foreground client sends interactive
    # shared-prefix requests. On the disaggregated topology the fresh
    # prefills run on the prefill replica and the decode replica pulls
    # each finished prefix over the fabric (one scatter + a tiny tail),
    # so the interactive stream's TTFT stops competing with long
    # prefills for the decode replica's step budget. Headlines:
    # interactive TTFT p99 / TPOT p99 per topology + the fabric hit
    # rate. (CPU proxy caveat: compute is width-linear here, so the
    # isolation win is structurally understated vs a TPU.)
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            import urllib.request

            from distributed_llm_inference_tpu.serving.router import (
                Replica, Router, RouterServer,
            )
            from distributed_llm_inference_tpu.serving.server import (
                InferenceServer,
            )

            dis_bs = 32
            shared_head = " ".join(f"warm{j}" for j in range(12)) + " "
            fresh_body = " ".join(f"load{j}" for j in range(28))

            def interactive_prompt(i):
                return shared_head + f"q{i:03d}"

            def fresh_prompt(i):
                return f"fresh{i:04d} " + fresh_body  # unique from byte 0

            def run_topology(classes):
                engines, reps = [], []
                for i, cls in enumerate(classes):
                    eng_x = InferenceEngine(
                        c_cfg, params=c_params,
                        engine_cfg=EngineConfig(
                            prefix_cache_entries=8, replica_class=cls,
                            kv_fabric_timeout_s=5.0,
                        ),
                    )
                    cont_x = ContinuousEngine(
                        eng_x, n_slots=n_slots, chunk_steps=chunk,
                        slot_max_seq=slot_max_seq,
                        kv_pool_blocks=pool_blocks, kv_block_size=dis_bs,
                    )
                    srv = InferenceServer(
                        eng_x, "127.0.0.1", 0, 64, continuous=cont_x
                    )
                    srv.start()
                    reps.append(Replica(
                        f"{cls[0]}{i}", f"http://127.0.0.1:{srv.port}",
                        replica_class=cls,
                    ))
                    engines.append((cont_x, srv))
                router = Router(
                    reps, probe_interval_s=3600.0,
                    request_timeout_s=120.0, handoff_min_bytes=128,
                )
                rserver = RouterServer(router, host="127.0.0.1", port=0)
                rserver.start()
                base = f"http://127.0.0.1:{rserver.port}"

                def post(payload):
                    req = urllib.request.Request(
                        base + "/generate",
                        data=json.dumps(payload).encode(),
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    )
                    try:
                        with urllib.request.urlopen(req, timeout=120) as r:
                            return json.loads(r.read())
                    except Exception:  # noqa: BLE001 - load gen only
                        return {}

                ia_kw = dict(max_tokens=8, greedy=True, chat=False)
                # warm every program + the shared head's blocks before
                # the timed window (standard leg discipline)
                post({"prompt": interactive_prompt(0), **ia_kw})
                post({"prompt": fresh_prompt(9999), "max_tokens": 2,
                      "greedy": True, "chat": False})
                stop = threading.Event()

                def churn():
                    i = 0
                    while not stop.is_set():
                        post({"prompt": fresh_prompt(i), "max_tokens": 2,
                              "greedy": True, "chat": False})
                        i += 1
                        time.sleep(0.01)

                th = threading.Thread(target=churn)
                th.start()
                ttfts, tpots = [], []
                try:
                    for i in range(1, 19):
                        r = post({"prompt": interactive_prompt(i), **ia_kw})
                        if r.get("status") == "success":
                            ttft = float(r["ttft_s"])
                            ttfts.append(ttft)
                            n = r["tokens_generated"]
                            el = float(str(r["time_taken"]).rstrip("s"))
                            if n > 1:
                                tpots.append(
                                    max(0.0, el - ttft) / (n - 1)
                                )
                finally:
                    stop.set()
                    th.join(timeout=120)
                fetches = hits = 0
                for cont_x, _ in engines:
                    st = cont_x.stats().get("kv_fabric") or {}
                    fetches += st.get("fetches", 0)
                    hits += st.get("hits", 0)
                handoffs = sum(
                    s["value"]
                    for s in router.metrics.snapshot().get(
                        "dli_router_handoffs_total", {}
                    ).get("series", [])
                )
                rserver.shutdown()
                for cont_x, srv in engines:
                    srv.shutdown()
                ttfts.sort()
                tpots.sort()

                def p99(xs):
                    return (
                        round(xs[min(len(xs) - 1, int(0.99 * len(xs)))], 5)
                        if xs else None
                    )

                return {
                    "ttft_p99_s": p99(ttfts),
                    "tpot_p99_s": p99(tpots),
                    "interactive_served": len(ttfts),
                    "fabric_fetches": fetches,
                    "fabric_hits": hits,
                    "fabric_hit_rate": (
                        round(hits / fetches, 3) if fetches else 0.0
                    ),
                    "handoffs": int(handoffs),
                }

            dis_leg = run_topology(["prefill", "decode"])
            mix_leg = run_topology(["mixed", "mixed"])
            cont_block["disagg"] = {
                "disaggregated": dis_leg, "mixed": mix_leg,
                "kv_block_size": dis_bs,
                "fresh_prompt_bytes": len(fresh_prompt(0)),
                "interactive_prompt_bytes": len(interactive_prompt(0)),
            }
            if dis_leg["ttft_p99_s"] and mix_leg["ttft_p99_s"]:
                cont_block["disagg_ttft_p99_s"] = dis_leg["ttft_p99_s"]
                cont_block["mixed_ttft_p99_s"] = mix_leg["ttft_p99_s"]
                cont_block["disagg_ttft_p99_improvement"] = round(
                    mix_leg["ttft_p99_s"] / dis_leg["ttft_p99_s"], 3
                )
            cont_block["disagg_fabric_hit_rate"] = dis_leg[
                "fabric_hit_rate"
            ]
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # Tracing-overhead leg (ISSUE 17): the dense continuous churn again,
    # now with every request carrying a client-minted trace context, at
    # --trace-sample-rate 0 / 0.1 / 1.0. Rate 0 is the always-on cost of
    # the seam itself (one deterministic float compare per submit; no
    # spans started, no launch notes) and is gated against this run's
    # OWN dense number (same prompts, same process, same compile cache):
    # off_within_1pct is the <=1% regression gate. The sampled rates
    # price launch-level attribution — launch.* spans keyed by dispatch
    # seq, host-side timestamps only, never a device sync — as tok/s and
    # client-observed TPOT p99.
    if (
        cont_block.get("dense_tokens_per_sec")
        and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S
    ):
        try:
            from distributed_llm_inference_tpu.utils.tracing import (
                SpanContext,
            )

            def tracing_leg(rate):
                eng_t = InferenceEngine(
                    c_cfg, params=c_params,
                    engine_cfg=EngineConfig(trace_sample_rate=rate),
                )
                cont_t = ContinuousEngine(
                    eng_t, n_slots=n_slots, chunk_steps=chunk,
                    slot_max_seq=slot_max_seq,
                )
                try:
                    cont_t.submit(prompts[0], **kw)  # warm slot programs
                    done = [0]
                    tpots = []
                    lock = threading.Lock()
                    it = iter(prompts)

                    def client():
                        while True:
                            with lock:
                                p = next(it, None)
                            if p is None:
                                return
                            tq = time.perf_counter()
                            r = cont_t.submit(
                                p, trace_ctx=SpanContext.new_root(), **kw
                            )
                            el = time.perf_counter() - tq
                            if r.get("status") == "success":
                                n = r["tokens_generated"]
                                with lock:
                                    done[0] += n
                                    if n > 1:
                                        tpots.append(
                                            max(
                                                0.0,
                                                el - float(r["ttft_s"]),
                                            ) / (n - 1)
                                        )

                    t0 = time.perf_counter()
                    threads = [
                        threading.Thread(target=client) for _ in range(8)
                    ]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    wall = time.perf_counter() - t0
                    tpots.sort()
                    return {
                        "tokens_per_sec": (
                            round(done[0] / wall, 3) if done[0] else None
                        ),
                        "tpot_p99_s": (
                            round(
                                tpots[
                                    min(
                                        len(tpots) - 1,
                                        int(0.99 * len(tpots)),
                                    )
                                ],
                                5,
                            ) if tpots else None
                        ),
                        # proves each rate did what it says: 0 spans at
                        # off, launch.* spans present when sampled
                        "spans_recorded": eng_t.trace_store.stats()[
                            "spans"
                        ],
                    }
                finally:
                    cont_t.close()

            trc = {
                "off": tracing_leg(0.0),
                "rate_0p1": tracing_leg(0.1),
                "rate_1p0": tracing_leg(1.0),
            }
            base = cont_block["dense_tokens_per_sec"]
            off_v = trc["off"]["tokens_per_sec"]
            if off_v:
                trc["off_vs_dense"] = round(off_v / base, 3)
                trc["off_within_1pct"] = bool(off_v >= 0.99 * base)
            on_v = trc["rate_1p0"]["tokens_per_sec"]
            if off_v and on_v:
                trc["sampled_overhead_frac"] = round(
                    1.0 - on_v / off_v, 3
                )
            cont_block["tracing_overhead"] = trc
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    if cont_block:
        result["continuous"] = cont_block
        # keep the round-3 flat key so round-over-round comparisons of the
        # dense-fleet number need no schema archaeology
        if "dense_tokens_per_sec" in cont_block:
            result["continuous_tokens_per_sec"] = cont_block[
                "dense_tokens_per_sec"
            ]

    # 1F1B microbatched-pipeline leg (parallel/schedule.py, BASELINE
    # config 5's schedule): pp=2 x microbatches=2 on a 2-virtual-CPU-device
    # mesh in a SUBPROCESS — its own process because the mesh needs
    # xla_force_host_platform_device_count, which must be set before the
    # backend initializes and must not perturb this process's single-device
    # measurements. Tiny model; direction-only round-over-round signal
    # (round-4 review #2). Never fatal.
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=2"
            )
            proc = subprocess.run(
                [sys.executable, "-c", _MB_LEG_SRC],
                capture_output=True, text=True, timeout=240, env=env,
            )
            line = next(
                (
                    ln for ln in reversed(proc.stdout.splitlines())
                    if ln.strip().startswith("{")
                ),
                None,
            )
            if proc.returncode == 0 and line:
                result["microbatch_1f1b"] = json.loads(line)
            else:
                sys.stderr.write(
                    f"1f1b leg rc={proc.returncode}: "
                    f"{(proc.stderr or '')[-800:]}\n"
                )
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # comms-contract cross-check leg (analysis/comms.py): derived static
    # bytes/launch per wire link vs the dli_pp_wire_bytes_total deltas a
    # real pp=2 run accumulates, wire off AND on, exact agreement
    # asserted IN the child. Same subprocess pattern as the 1f1b leg
    # (the 2-device mesh needs xla_force_host_platform_device_count
    # before backend init). Never fatal.
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=2"
            )
            proc = subprocess.run(
                [sys.executable, "-c", _COMMS_LEG_SRC],
                capture_output=True, text=True, timeout=240, env=env,
            )
            line = next(
                (
                    ln for ln in reversed(proc.stdout.splitlines())
                    if ln.strip().startswith("{")
                ),
                None,
            )
            if proc.returncode == 0 and line:
                result["comms_report"] = json.loads(line)
            else:
                sys.stderr.write(
                    f"comms leg rc={proc.returncode}: "
                    f"{(proc.stderr or '')[-800:]}\n"
                )
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # MPMD stage-pipeline leg (serving/stage_runtime.py): real 2-process
    # stage fleet over the HTTP transport vs the single-process forward
    # loop — TTFT/TPOT p99 per topology, transcript bit-identity, and
    # timed kill -9 recovery (warm block-shadow restore vs cold), see
    # _MPMD_LEG_SRC. Own subprocess (the stage fleet spawns its own
    # children; the leg's jax must not inherit this process's device
    # config). Never fatal.
    if time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
            proc = subprocess.run(
                [sys.executable, "-c", _MPMD_LEG_SRC],
                capture_output=True, text=True, timeout=300, env=env,
            )
            line = next(
                (
                    ln for ln in reversed(proc.stdout.splitlines())
                    if ln.strip().startswith("{")
                ),
                None,
            )
            if proc.returncode == 0 and line:
                result["mpmd_pipeline"] = json.loads(line)
            else:
                sys.stderr.write(
                    f"mpmd leg rc={proc.returncode}: "
                    f"{(proc.stderr or '')[-800:]}\n"
                )
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    # tiered-KV leg (engine/shadow.py HBM -> host -> disk; ISSUE r16):
    # a Zipf(alpha=1.0) long-tail prefix workload over a population far
    # wider than the HBM pool, served three ways — pool-only (kv_shadow
    # off), +host shadow, +host+disk — giving the hit-rate-vs-tier-depth
    # curve; then disk-warm-vs-cold TTFT on a long chain through a fresh
    # engine over the SAME chunk-file dir (the crash-restart shape), and
    # streamed vs whole-blob /kv pull timing on that chain. CPU: tiny
    # model, direction-only round-over-round signal. Never fatal.
    if cont_block and time.perf_counter() - T_START < BATCH_LEG_DEADLINE_S:
        try:
            import random as _random
            import shutil as _shutil
            import tempfile as _tempfile
            import urllib.request as _urlreq

            from distributed_llm_inference_tpu.serving.server import (
                InferenceServer,
            )

            from distributed_llm_inference_tpu.models import api as _M

            rng = _random.Random(16)
            KBS = 16
            POP, REQS = 24, 48
            zw = [1.0 / (r + 1) for r in range(POP)]  # Zipf alpha=1.0
            fam = [
                f"tier bench family {i:02d} prefix body text " * 2 + "go"
                for i in range(POP)
            ]  # ~80 chars -> 5 full 16-token blocks each
            order = rng.choices(range(POP), weights=zw, k=REQS)
            kw_t = dict(max_tokens=8, greedy=True, chat=False)
            tmp_disk = _tempfile.mkdtemp(prefix="dli-kvtier-")
            tmp_disk2 = _tempfile.mkdtemp(prefix="dli-kvtier-deep-")
            kvt = {
                "model": c_cfg.name, "platform": platform,
                "block_size": KBS, "pool_blocks": 26,
                "slot_max_seq": 128,
                "host_blocks": 48, "population": POP,
                "requests": REQS, "zipf_alpha": 1.0,
            }

            # curve variants: a deliberate capacity LADDER — pool (25
            # usable blocks, ~4 families) < host tier (56 blocks, ~11
            # families) < disk (unbounded) — against a 24-family x
            # 5-block prefix population, so each deeper tier can only
            # add hit rate the shallower one lacks the capacity for,
            # and the host tier churns enough to demote onto disk.
            def tier_variant(shadow, disk_dir, cfg_v=None, params_v=None,
                             pool=26, slot=128, host=48):
                eng_t = InferenceEngine(
                    cfg_v if cfg_v is not None else c_cfg,
                    params=params_v if params_v is not None else c_params,
                    engine_cfg=EngineConfig(
                        prefix_cache_entries=64, kv_shadow=shadow,
                        kv_shadow_blocks=host, kv_disk_dir=disk_dir,
                    ),
                )
                cont_t = ContinuousEngine(
                    eng_t, n_slots=2, chunk_steps=8, slot_max_seq=slot,
                    kv_pool_blocks=pool, kv_block_size=KBS,
                )
                return eng_t, cont_t

            def zipf_pass(cont_t):
                cont_t.submit(fam[0], **kw_t)  # warm slot programs
                cached = total = 0
                for i in order:
                    r = cont_t.submit(fam[i], **kw_t)
                    if r.get("status") == "success":
                        cached += r.get("prefix_cached_tokens", 0)
                        total += 5 * KBS  # full blocks per family prompt
                return (round(cached / total, 3) if total else None)

            curve = {}
            eng_t, cont_t = tier_variant(False, None)
            try:
                curve["pool_only"] = zipf_pass(cont_t)
            finally:
                cont_t.close()
            eng_t, cont_t = tier_variant(True, None)
            try:
                curve["host"] = zipf_pass(cont_t)
                cont_t._shadow.flush(10.0)
                sh = cont_t._shadow.stats()
                # host-only churn ledger: evictions here DROP (no tier
                # below) — the delta the +disk variant recovers
                kvt["host_variant_counters"] = {
                    k: sh[k] for k in ("copied", "evicted", "dropped")
                }
            finally:
                cont_t.close()
            eng_t, cont_t = tier_variant(True, tmp_disk)
            try:
                curve["host_disk"] = zipf_pass(cont_t)
                cont_t._shadow.flush(10.0)
                sh = cont_t._shadow.stats()
                kvt["tier_counters"] = {
                    k: sh[k] for k in (
                        "copied", "evicted", "demoted", "promoted",
                        "disk_hits", "disk_blocks", "disk_bytes", "dropped",
                    )
                }
            finally:
                cont_t.close()
            kvt["hit_rate_curve"] = curve

            # disk-warm vs cold TTFT, on a DEEP chain (118 blocks at a
            # 2048-token window — the regime the disk tier exists for:
            # cold re-prefill cost grows superlinearly with depth while
            # promotion stays one parallel chunk-file read + one batched
            # restore launch). Seed engine runs the chain once and
            # gracefully drains its host tier to disk; a FRESH engine
            # over the same chunk dir (the crash-restart shape) rescans
            # tier 2 and promotes at admission; the cold engine
            # re-prefills the whole chain.
            c_cfg_t = get_model_config(
                "test-llama-tiny", dtype="float32", eos_token_id=-1,
                max_seq_len=2048,
            )
            c_params_t = _M.init_params(c_cfg_t, jax.random.PRNGKey(2))
            long_prompt = "deep chain segment data " * 79 + "end!"
            deep_kw = dict(
                cfg_v=c_cfg_t, params_v=c_params_t,
                pool=260, slot=2048, host=160,
            )
            kvt["deep_chain"] = {
                "max_seq_len": 2048, "pool_blocks": 260,
                "host_blocks": 160,
            }
            eng_s, cont_s = tier_variant(True, tmp_disk2, **deep_kw)
            deep = None
            try:
                r_long = cont_s.submit(long_prompt, **kw_t)
                deep = (r_long.get("kv_digests") or [None])[-1]
                cont_s._shadow.flush(10.0)
                kvt["drained_to_disk"] = cont_s._shadow.demote_host_tier()
                kvt["long_chain_tier_at_seed_close"] = (
                    cont_s._shadow.digest_tier(deep) if deep else None
                )
            finally:
                cont_s.close()
            eng_w, cont_w = tier_variant(True, tmp_disk2, **deep_kw)
            try:
                cont_w.submit(fam[0], **kw_t)  # warm slot programs
                r_w = cont_w.submit(long_prompt, **kw_t)
                eng_c, cont_c = tier_variant(True, None, **deep_kw)
                try:
                    cont_c.submit(fam[0], **kw_t)  # warm programs
                    r_c = cont_c.submit(long_prompt, **kw_t)
                finally:
                    cont_c.close()
                if (
                    r_w.get("status") == "success"
                    and r_c.get("status") == "success"
                ):
                    warm, cold = float(r_w["ttft_s"]), float(r_c["ttft_s"])
                    kvt["ttft"] = {
                        "disk_warm_s": round(warm, 5),
                        "cold_s": round(cold, 5),
                        "promoted_blocks": r_w.get(
                            "kv_promoted_blocks", 0
                        ),
                        "speedup": (
                            round(cold / warm, 2) if warm > 0 else None
                        ),
                        "warm_ge_2x": bool(warm > 0 and cold >= 2 * warm),
                    }

                # streamed vs whole-blob /kv pull on the same long chain
                # (now host-resident after the warm promotion): time to
                # first importable byte is the number decode overlap
                # actually sees
                if deep:
                    srv_t = InferenceServer(
                        eng_w, "127.0.0.1", 0, max_tokens_cap=64,
                        continuous=cont_w,
                    )
                    srv_t.start()
                    try:
                        base = f"http://127.0.0.1:{srv_t.port}/kv/{deep}"

                        def pull(streamed):
                            req = _urlreq.Request(base)
                            if streamed:
                                req.add_header("X-KV-Stream", "1")
                            t0 = time.perf_counter()
                            with _urlreq.urlopen(req, timeout=30) as resp:
                                first = resp.read(9)
                                t1 = time.perf_counter()
                                body = first + resp.read()
                                t2 = time.perf_counter()
                            return t1 - t0, t2 - t0, len(body)

                        # warm both paths once (encode caches, TCP stack)
                        pull(False), pull(True)
                        b_first, b_total, b_len = pull(False)
                        s_first, s_total, s_len = pull(True)
                        kvt["pull"] = {
                            "chain_blocks": r_w.get(
                                "kv_promoted_blocks", 0
                            ),
                            "blob_first_byte_s": round(b_first, 5),
                            "blob_total_s": round(b_total, 5),
                            "blob_bytes": b_len,
                            "stream_first_byte_s": round(s_first, 5),
                            "stream_total_s": round(s_total, 5),
                            "stream_bytes": s_len,
                            "stream_first_byte_speedup": (
                                round(b_first / s_first, 2)
                                if s_first > 0 else None
                            ),
                        }
                    finally:
                        srv_t.shutdown()
            finally:
                cont_w.close()
                _shutil.rmtree(tmp_disk, ignore_errors=True)
                _shutil.rmtree(tmp_disk2, ignore_errors=True)
            result["kv_tiers"] = kvt
        except Exception:  # noqa: BLE001 - optional leg, never fatal
            import traceback

            traceback.print_exc(file=sys.stderr)

    _emit(result)


if __name__ == "__main__":
    run_benchmark()
