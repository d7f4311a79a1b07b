#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Run from the repo root on a machine with a TPU:

    python chip_smoke.py               # one chip (what the driver runs)
    python chip_smoke.py --four-chips  # pp=4 against a one-chip reference

It starts the server the way a user does — `python -m
distributed_llm_inference_tpu.serving.server --continuous ... --kv-pool-blocks
... --prefix-cache ... --attn-impl pallas --warmup` — as a CHILD process with
`JAX_PLATFORMS=tpu` (so JAX fails instead of falling back to the CPU), waits on
/ready, and drives it over HTTP with `distributed_llm_inference_tpu.client`:
TinyLlama-1.1B at its full published width and depth, bf16, random weights
from the seed, offline byte tokenizer, a KV pool of >= 1 GiB. This process
never initialises a JAX backend: the chip belongs to one process at a time,
and that process is the server.

One chip (default): the pallas server answers six requests (short; longer
than one prefill chunk; two concurrent; one streamed; one repeat that must hit
the prefix cache), then a second server with `--attn-impl xla` answers the
same requests and the two are compared; a last brief restart of the first
configuration up to /ready shows whether the persistent compile cache hits.

Four chips (`--four-chips`): ONLY a one-chip reference server and a `--pp 4`
server with the same flags, the same requests and the same criterion, plus
each device's bytes_in_use.

THE AGREEMENT CRITERION, and why it is this one. Two different bf16 attention
implementations on random weights do not owe each other bit-equal greedy
text (a near-tie flips and the continuations diverge), and at vocab 32000 the
byte tokenizer renders almost every token as nothing, so text says little
anyway. Both checks below are therefore teacher-forced — the tokens are fixed
by the prompt, only the arithmetic differs:

  1. SCORING: `/v1/completions` echo + logprobs over the long prompt (the
     engine's score path: dense cache, flash kernel on every chunk under
     pallas). Per-token logprobs must agree within LP_MEAN_TOL on average and
     LP_MAX_TOL at worst, and the top-1 entries must agree at TOP1_MIN of the
     positions.
  2. PAGED KV: the KV blocks the continuous -> scheduler -> paged -> ragged
     kernel path wrote for the long prompt (several chunked-prefill steps,
     each reading the earlier blocks through the block table) and for the
     prefix-hit repeat (new blocks computed ON TOP of shared cached blocks),
     fetched by content digest from `GET /kv/{digest}` and decoded with the
     repo's own wire format. K/V at layer l are a function of every attention
     output below l, so for each (layer, token) the relative L2 distance of
     the two servers' vectors must stay under KV_TOL.

The tolerances come from the dtype: bf16 rounds at 2^-8 ~ 4e-3 relative, and
~4 roundings a layer over 22 layers walk to a few 1e-2 at worst. A wrong mask
or block table is not a rounding: a query at position t that sees one key too
many or too few moves its attention output by ~1/(t+1) (tens of percent at
the first positions of every prompt, whatever the prompt length), and a walk
through the wrong block replaces whole keys — both land far outside KV_TOL at
the early tokens of every layer above the first.

Greedy text and token counts are printed for both servers and compared for
information only.

Exit code 0 and a last line `{"ok": true, "device": {...}}` only when every
phase passed AND the serving process reports `platform: "tpu"` on /health.
`--model test-llama-tiny --platform cpu` rehearses the whole flow off the chip
(and then exits non-zero: the device is not a TPU).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from distributed_llm_inference_tpu.client import DistributedLLMClient  # noqa: E402
from distributed_llm_inference_tpu.config import stage_layer_range  # noqa: E402
from distributed_llm_inference_tpu.models.registry import get_model_config  # noqa: E402
from distributed_llm_inference_tpu.serving.kv_fabric import decode_chain  # noqa: E402
from distributed_llm_inference_tpu.utils import compile_cache  # noqa: E402

# bf16 tolerances (see the module docstring for where they come from)
LP_MEAN_TOL = 0.05  # mean |delta logprob| over the scored prompt
LP_MAX_TOL = 0.25  # worst |delta logprob|
TOP1_MIN = 0.75  # share of positions whose top-1 entry agrees
KV_TOL = 0.05  # worst per-(layer, token) relative L2 distance of K and V

BLOCK = 16  # --kv-block-size
SLOTS = 8  # --continuous
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

_WORDS = (
    "pipeline stage shard block cache prefix token layer tensor mesh ring "
    "kernel queue batch slot chunk window budget decode prefill router "
    "replica digest fabric shadow tile grid scalar vector matrix"
).split()


def say(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def make_text(rng: random.Random, n_bytes: int) -> str:
    """Seeded ASCII prose of exactly n_bytes (= n_bytes byte-tokens)."""
    out = []
    size = 0
    while size < n_bytes:
        w = rng.choice(_WORDS) + str(rng.randrange(10))
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries() -> int:
    d = compile_cache.cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


class Server:
    """One serving process: started like a user starts it, stopped with
    SIGTERM (the graceful drain), killed if that does not end it."""

    def __init__(self, name: str, args, extra: list):
        self.name = name
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(OUT_DIR, f"server_{name}.log")
        self.cmd = [
            sys.executable, "-m", "distributed_llm_inference_tpu.serving.server",
            "--model", args.model, "--dtype", "bfloat16",
            "--host", "127.0.0.1", "--port", str(self.port),
            "--seed", str(args.seed),
            "--continuous", str(SLOTS),
            "--kv-pool-blocks", str(args.pool_blocks),
            "--kv-block-size", str(BLOCK),
            "--prefix-cache", "8", "--warmup",
        ] + extra
        self.env = dict(os.environ, JAX_PLATFORMS=args.platform)
        if args.platform == "cpu":
            # the rehearsal: kernels interpreted, four virtual devices
            self.env.setdefault("DLI_PALLAS_INTERPRET", "1")
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            )
        self.proc = None
        self.ready_s = None

    def __enter__(self):
        os.makedirs(OUT_DIR, exist_ok=True)
        say(f"[{self.name}] $ {' '.join(self.cmd[1:])}")
        self._log = open(self.log_path, "w")
        t0 = time.time()
        self.proc = subprocess.Popen(
            self.cmd, cwd=HERE, env=self.env,
            stdout=self._log, stderr=subprocess.STDOUT,
        )
        deadline = t0 + 900
        while True:
            if self.proc.poll() is not None:
                fail(
                    f"[{self.name}] server exited with code "
                    f"{self.proc.returncode} before /ready:\n{self.log_tail()}"
                )
            try:
                with urllib.request.urlopen(self.url + "/ready", timeout=2) as r:
                    if r.status == 200:
                        break
            except (urllib.error.URLError, OSError):
                pass
            if time.time() > deadline:
                fail(f"[{self.name}] not ready after 900 s:\n{self.log_tail()}")
            time.sleep(0.5)
        self.ready_s = time.time() - t0
        return self

    def __exit__(self, *exc):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()
        return False

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def get(self, path: str, raw: bool = False, timeout: float = 60):
        with urllib.request.urlopen(self.url + path, timeout=timeout) as r:
            data = r.read()
        return data if raw else json.loads(data)

    def post(self, path: str, body: dict, timeout: float = 600) -> dict:
        req = urllib.request.Request(
            self.url + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())


def check_envelope(tag: str, out: dict, want_tokens: int) -> dict:
    if out.get("status") != "success":
        fail(f"{tag}: status {out.get('status')!r}: {out.get('error')}")
    if out.get("tokens_generated") != want_tokens:
        fail(
            f"{tag}: asked {want_tokens} tokens, got "
            f"{out.get('tokens_generated')} (finish {out.get('finish_reason')})"
        )
    if out.get("backend") != "continuous":
        fail(f"{tag}: served by {out.get('backend')!r}, not the continuous fleet")
    return out


def device_bytes(srv: Server) -> list:
    """[(device, bytes_in_use)] from /workers, in stage order."""
    rows = []
    for stage in srv.get("/workers")["detail"]:
        for dev, mem in zip(stage["devices"], stage["memory"]):
            rows.append((dev, mem.get("bytes_in_use")))
    return rows


def _as_f32(a) -> np.ndarray:
    a = np.asarray(a)
    if a.dtype.kind == "V" and a.dtype.itemsize == 2:
        # the wire's npz carries a bf16 pool's blocks as raw 2-byte voids
        import ml_dtypes

        a = a.view(ml_dtypes.bfloat16)
    return a.astype(np.float32)


def fetch_chain(srv: Server, digest: str) -> tuple:
    """(K, V) float32 arrays [n_layers_stored, tokens, KV*Dh] of one
    shadowed chain. The shadow copy is asynchronous: a 404 right after
    the response means "not landed yet", so poll briefly."""
    deadline = time.time() + 60
    while True:
        try:
            data = srv.get(f"/kv/{digest}", raw=True)
            break
        except urllib.error.HTTPError as e:
            if e.code != 404 or time.time() > deadline:
                fail(f"[{srv.name}] GET /kv/{digest}: HTTP {e.code}")
            time.sleep(0.25)
    _, blocks = decode_chain(data, BLOCK, digest)  # verifies the content key
    out = []
    for leaf in (0, 1):  # k, v: each block [L, KV, bs, Dh]
        a = np.stack([_as_f32(b[leaf]) for b in blocks])
        n, L, KV, bs, Dh = a.shape
        out.append(a.transpose(1, 0, 3, 2, 4).reshape(L, n * bs, KV * Dh))
    return tuple(out)


def real_layers(n_stored: int, n_layers: int, pp: int) -> list:
    """Indices of the model's real layers in a stored layer axis: a pp mesh
    pads each stage's share to ceil(n_layers / pp) (parallel/partition.py),
    so its pool carries zero layers the single chip's does not."""
    if n_stored == n_layers:
        return list(range(n_layers))
    per = -(-n_layers // pp)
    if n_stored != per * pp:
        fail(f"KV chain stores {n_stored} layers; expected {n_layers} or {per * pp}")
    idx = []
    for s in range(pp):
        lo, hi = stage_layer_range(n_layers, pp, s)
        idx.extend(s * per + i for i in range(hi - lo))
    return idx


def drive(srv: Server, args, prompts: dict) -> dict:
    """The six requests + the scoring call + the KV chains of one server."""
    cl = DistributedLLMClient(srv.url, timeout=600)
    n = args.n_tokens
    kw = dict(max_tokens=n, greedy=True, chat=False, verbose=False)
    res = {}
    t0 = time.time()
    res["short"] = check_envelope("short", cl.generate(prompts["short"], **kw), n)
    res["long"] = check_envelope("long", cl.generate(prompts["long"], **kw), n)
    if res["long"]["prompt_tokens"] <= args.chunk:
        fail(
            f"long prompt is {res['long']['prompt_tokens']} tokens: not longer "
            f"than one prefill chunk ({args.chunk})"
        )
    pair = {}

    def one(tag):
        pair[tag] = cl.generate(prompts[tag], **kw)

    threads = [threading.Thread(target=one, args=(t,)) for t in ("pair_a", "pair_b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
        if t.is_alive():
            fail("a concurrent request did not finish in 600 s")
    for tag in ("pair_a", "pair_b"):
        res[tag] = check_envelope(tag, pair[tag], n)
    res["stream"] = check_envelope(
        "stream",
        cl.generate_stream(prompts["stream"], max_tokens=n, greedy=True, chat=False),
        n,
    )
    res["repeat"] = check_envelope("repeat", cl.generate(prompts["repeat"], **kw), n)
    cached = res["repeat"].get("prefix_cached_tokens", 0)
    if not cached:
        fail("the repeat did not hit the prefix cache (prefix_cached_tokens == 0)")
    say(
        f"[{srv.name}] 6 requests ok in {time.time() - t0:.1f}s: "
        f"{n} tokens each; long prompt {res['long']['prompt_tokens']} tokens "
        f"(> chunk {args.chunk}); repeat prefix_cached_tokens={cached}"
    )
    score = srv.post("/v1/completions", {
        "prompt": prompts["long"], "max_tokens": 0, "echo": True,
        "logprobs": 1, "temperature": 0,
    })
    lp = score["choices"][0]["logprobs"]
    res["logprobs"] = np.array(lp["token_logprobs"][1:], np.float64)
    res["top1"] = [
        next(iter(d.items())) for d in lp["top_logprobs"][1:]
    ]
    if not np.all(np.isfinite(res["logprobs"])):
        fail(f"[{srv.name}] non-finite logprobs from the score path")
    for tag in ("long", "repeat"):
        res[f"kv_{tag}"] = fetch_chain(srv, res[tag]["kv_digests"][-1])
        if not all(np.all(np.isfinite(a)) for a in res[f"kv_{tag}"]):
            fail(f"[{srv.name}] non-finite KV in the {tag} chain")
    say(
        f"[{srv.name}] scored {len(res['logprobs'])} prompt tokens; KV chains: "
        f"long {res['kv_long'][0].shape}, repeat {res['kv_repeat'][0].shape} "
        f"[layers, tokens, KV*Dh]"
    )
    return res


def compare(name_a: str, a: dict, name_b: str, b: dict, n_layers: int, pp_b: int):
    """Hold two servers' results to the criterion of the module docstring."""
    d = np.abs(a["logprobs"] - b["logprobs"])
    top1 = np.mean([
        ta[0] == tb[0] and abs(ta[1] - tb[1]) <= LP_MAX_TOL
        for ta, tb in zip(a["top1"], b["top1"])
    ])
    say(
        f"agreement {name_a} vs {name_b} — score path, {d.size} tokens: "
        f"mean|dlogprob|={d.mean():.5f} (tol {LP_MEAN_TOL}) "
        f"max|dlogprob|={d.max():.5f} (tol {LP_MAX_TOL}) "
        f"top1 agreement={top1:.3f} (min {TOP1_MIN})"
    )
    ok = d.mean() <= LP_MEAN_TOL and d.max() <= LP_MAX_TOL and top1 >= TOP1_MIN
    for tag in ("long", "repeat"):
        worst = 0.0
        for leaf, (xa, xb) in enumerate(zip(a[f"kv_{tag}"], b[f"kv_{tag}"])):
            xa = xa[real_layers(xa.shape[0], n_layers, 1)]
            xb = xb[real_layers(xb.shape[0], n_layers, pp_b)]
            if xa.shape != xb.shape:
                fail(f"KV chain shapes differ: {xa.shape} vs {xb.shape}")
            num = np.linalg.norm(xa - xb, axis=-1)
            den = np.maximum(np.linalg.norm(xb, axis=-1), 1e-6)
            rel = num / den  # [layers, tokens]
            layer, tok = np.unravel_index(np.argmax(rel), rel.shape)
            say(
                f"agreement {name_a} vs {name_b} — paged {'KV'[leaf]} of the "
                f"{tag} chain, {rel.shape[0]} layers x {rel.shape[1]} tokens: "
                f"worst rel L2={rel.max():.5f} at layer {layer} token {tok} "
                f"(tol {KV_TOL}); mean={rel.mean():.5f}; "
                f"last layer mean={rel[-1].mean():.5f}"
            )
            worst = max(worst, float(rel.max()))
        ok = ok and worst <= KV_TOL
    same = sum(
        a[t]["response"] == b[t]["response"]
        for t in ("short", "long", "pair_a", "pair_b", "stream", "repeat")
    )
    say(f"(information only) greedy text identical in {same}/6 requests")
    if not ok:
        fail(f"{name_a} and {name_b} disagree beyond the stated tolerance")


def report_device(srv: Server) -> dict:
    h = srv.get("/health")
    dev = h["device"]
    say(
        f"[{srv.name}] ready in {srv.ready_s:.1f}s — model {h['model']} "
        f"backend {h['backend']} stages {h['n_stages']} device {json.dumps(dev)}"
    )
    for name, used in device_bytes(srv):
        say(f"[{srv.name}]   {name}: bytes_in_use={used}")
    return dev


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="tinyllama-1.1b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run ONLY the pp=4 server and its one-chip reference",
    )
    ap.add_argument(
        "--platform", default="tpu", choices=["tpu", "cpu"],
        help="JAX_PLATFORMS for the server processes; cpu rehearses the "
             "flow (interpreted kernels) and always ends non-zero",
    )
    args = ap.parse_args()

    cfg = get_model_config(args.model)
    ctx = cfg.max_seq_len
    real = ctx >= 2048
    # >= 1 GiB of bf16 KV at TinyLlama widths (22.5 KB/token): 3072 x 16
    args.pool_blocks = 3072 if real else 4 * (ctx // BLOCK + 1)
    args.chunk = 128  # EngineConfig.step_token_budget: one prefill chunk
    args.n_tokens = 24 if real else 6
    rng = random.Random(args.seed)
    long_len = 3 * args.chunk + 40 if real else ctx - 4 * BLOCK
    long_prompt = make_text(rng, long_len)
    prompts = {
        "short": make_text(rng, 40),
        "long": long_prompt,
        "pair_a": make_text(rng, 150 if real else 50),
        "pair_b": make_text(rng, 90 if real else 30),
        "stream": make_text(rng, 60 if real else 24),
        # the long prompt again, plus a tail of two more blocks: must reuse
        # the cached blocks and compute new ones on top of them
        "repeat": long_prompt + " " + make_text(rng, 2 * BLOCK + 3),
    }
    if not real:
        args.chunk = min(args.chunk, long_len - 1)
    say(
        f"chip_smoke: model {args.model} (layers {cfg.n_layers}, d {cfg.dim}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, ctx {ctx}) "
        f"bf16, seed {args.seed}, pool {args.pool_blocks} x {BLOCK} tokens, "
        f"platform {args.platform}, four_chips {args.four_chips}"
    )
    say(
        f"compile cache: {compile_cache.cache_dir()} "
        f"({'from ' + compile_cache.ENV_VAR if os.environ.get(compile_cache.ENV_VAR) else 'checkout default'}), "
        f"{cache_entries()} entries before the first start"
    )

    pallas = ["--attn-impl", "pallas"]
    if args.four_chips:
        say(
            "four chips: every serving flag of the one-chip phase is served "
            "on a pp mesh (README composition matrix); none is substituted"
        )
        with Server("one-chip", args, pallas) as srv:
            report_device(srv)
            ref = drive(srv, args, prompts)
        with Server("pp4", args, pallas + ["--pp", "4"]) as srv:
            dev = report_device(srv)
            rows = device_bytes(srv)
            got = drive(srv, args, prompts)
        if len(rows) != 4:
            fail(f"--pp 4 reports {len(rows)} devices, not 4")
        compare("one-chip", ref, "pp4", got, cfg.n_layers, 4)
    else:
        n0 = cache_entries()
        with Server("pallas", args, pallas) as srv:
            dev = report_device(srv)
            cold_s, n1 = srv.ready_s, cache_entries()
            ref = drive(srv, args, prompts)
        with Server("xla", args, ["--attn-impl", "xla"]) as srv:
            report_device(srv)
            got = drive(srv, args, prompts)
        compare("pallas", ref, "xla", got, cfg.n_layers, 1)
        n2 = cache_entries()
        with Server("pallas-again", args, pallas) as srv:
            report_device(srv)
            warm_s, n3 = srv.ready_s, cache_entries()
        say(
            f"compile cache: cold start ready in {cold_s:.1f}s "
            f"({n0} -> {n1} entries), same configuration again ready in "
            f"{warm_s:.1f}s ({n2} -> {n3} entries)"
        )
        if n3 != n2:
            fail(
                f"the repeated start of the same configuration added "
                f"{n3 - n2} entries to the compile cache: the persistent cache missed"
            )
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            fail("the parent process initialised a JAX backend")
    if dev["platform"] != "tpu":
        fail(f"every phase passed, but on {dev['platform']!r}, not a TPU")
    say(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
