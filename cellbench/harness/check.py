"""The output check: what decides `correct`.

After the window and the drain, outside every timing, the live server
answers a small seeded sample of greedy requests at the published widths,
made to walk the layers the cells' `why` lines name:

  * `long`    a prompt of several prefill chunks (chunked prefill: each
              chunk's attention reads the earlier chunks' blocks through
              the block table, in mixed steps with the decode rows below),
              then some tens of generated tokens;
  * `repeat`  the same prompt again with a fresh tail, sent when `long` has
              finished: it must hit the block-prefix index (the envelope's
              `prefix_cached_tokens`), and what it generates is computed on
              top of shared cached blocks;
  * `decode`  short prompts that generate a few hundred tokens while the
              two above prefill: decode rows of the paged kernels, through
              the pool, step after step.

The benchmark's word-level tokenizer renders token <id> as the word
`w<id>`, so the response text IS the generated token ids. The server is
then stopped and `harness/ref_child.py` runs the plain float32 reference
(cellbench/reference/) on the chip, from the seed alone, teacher-forced on
prompt + generated tokens. Every generated token is held against the
reference's own logits at the position before it: `margin` = the
reference's best logit minus its logit of the token the server chose, in
units of the logits' standard deviation; 0 where the server chose the
reference's top-1. A token can only be right if every layer, the cache it
read (prefill chunks, shared prefix blocks, earlier decode writes), the
final norm, the output head and the sampler were right, so a wrong mask,
block table, window or rotation drives margins to several sigmas at once.

THE NUMBERS COMPARED AND THEIR LIMITS, over all generated rows of a run:
`mismatch` = share of rows whose margin is above 0 (bf16 rounding flips a
near-tie now and then; a lower precision flips many more), `mean` = mean
margin, `worst` = the largest. The limits are per configuration and live in
its file under "check"; PERF.md gives, for each, the largest reading of
sound runs, the smallest of the control, and the limit between them. The
control is the plain reference computed with 8-bit weights and put in the
program's place (cellbench/tools/control.py): the program's own
`--quant int8` cannot load these models on one chip.

What this leaves unseen: numbers are compared only through the choices
they lead to, so an error too small to flip a near-tie is not seen (the
sharper comparison, K and V fetched from `GET /kv/{digest}` against the
reference's, worked on the chip but needs the host shadow store, which
PERF.md section 6 shows cannot be left on at these sizes).
"""

from __future__ import annotations

import json
import os
import random
import shutil
import sys
import threading
import time

from harness import launcher
from harness.manifest import BENCH_DIR
from harness.traffic_lib import Request, Words

DEFAULT_SAMPLE = {
    "long_tokens": 700, "repeat_extra_tokens": 200, "prefill_max_tokens": 48,
    "decode": [{"tokens": 100, "max_tokens": 160}, {"tokens": 40, "max_tokens": 128},
               {"tokens": 70, "max_tokens": 128}],
    "min_rows": 200,
}


def parse_words(text: str):
    """Token ids of a response rendered by the word-level tokenizer, or
    None if anything in it is not a word `w<id>`."""
    ids = []
    for w in text.split():
        if w[:1] != "w" or not w[1:].isdigit():
            return None
        ids.append(int(w[1:]))
    return ids


def generated_ids(res) -> tuple:
    """(ids, holes): the generated token ids read off the stream, chunk by
    chunk. A chunk that carries fewer words than tokens holds a special
    token (it has no word, and where it sat cannot be told): the ids stop
    before that chunk, and `holes` says so."""
    ids, prev = [], 0
    for n, text in res.deltas:
        words = parse_words(text)
        if words is None or len(words) != n - prev:
            return ids, True
        ids.extend(words)
        prev = n
    return ids, prev != res.tokens


def collect(fleet_factory, sample: dict, words: Words, seed: int, expect_hit: bool,
            say) -> list:
    """Drive the check's requests. Returns [{"name", "ids", "n_prompt"}]; a
    sequence that cannot be used carries "error" (judged as failed) or
    fewer ids than it generated where a special token left a hole."""
    rng = random.Random(f"{seed}:check")
    sample = {**DEFAULT_SAMPLE, **(sample or {})}
    long_ids = words.ids(rng, int(sample["long_tokens"]))
    rep_ids = long_ids + words.ids(rng, int(sample["repeat_extra_tokens"]))
    pre_max = int(sample["prefill_max_tokens"])
    prompts = {"long": (long_ids, pre_max), "repeat": (rep_ids, pre_max)}
    for i, d in enumerate(sample["decode"]):
        prompts[f"decode{i}"] = (words.ids(rng, int(d["tokens"])), int(d["max_tokens"]))
    fleet = fleet_factory()
    results = {}

    def one(name):
        ids, max_tokens = prompts[name]
        req = Request(prompt=Words.text(ids), n_prompt=len(ids), max_tokens=max_tokens)
        results[name] = fleet.send(req, time.monotonic())

    threads = [threading.Thread(target=one, args=(n,), daemon=True)
               for n in prompts if n.startswith("decode")]
    for t in threads:
        t.start()
    time.sleep(0.05)  # the decode rows are in flight when the long prefill arrives
    one("long")
    one("repeat")
    for t in threads:
        t.join(timeout=300)
    seqs = []
    for name, (ids, _) in prompts.items():
        res = results.get(name)
        if res is None or not res.ok:
            seqs.append({"name": name, "error":
                         f"request {getattr(res, 'status', 'never answered')}: "
                         f"{getattr(res, 'error', '')}"})
            continue
        gen, holes = generated_ids(res)
        if res.prompt_tokens != len(ids):
            seqs.append({"name": name, "error": f"the server counted {res.prompt_tokens} "
                         f"prompt tokens, the prompt has {len(ids)}"})
            continue
        if holes:
            say(f"check {name}: {res.tokens} tokens generated, the first {len(gen)} of them "
                f"compared: the next chunk holds a special token, which has no word")
        if gen:
            seqs.append({"name": name, "ids": ids + gen, "n_prompt": len(ids)})
    say("check requests: " + ", ".join(
        f"{n} {r.prompt_tokens}+{r.tokens}" for n, r in results.items())
        + f"; repeat prefix_cached_tokens={results['repeat'].cached_tokens}")
    rep = results["repeat"]
    if expect_hit and rep.ok and rep.cached_tokens <= 0:
        seqs.append({"name": "repeat-hit", "error": "the repeat did not hit the prefix cache"})
    rows = sum(len(s["ids"]) - s["n_prompt"] for s in seqs if "ids" in s)
    if rows < int(sample["min_rows"]):
        seqs.append({"name": "rows", "error": f"only {rows} generated rows to compare, "
                     f"under {sample['min_rows']}"})
    return seqs


def run_reference(seqs: list, config_path: str, seed: int, platform: str,
                  cache_dir: str, tag: str, say) -> dict:
    """Write the sequences down, run the reference child, read its margins."""
    d = os.path.join(launcher.state_dir(), "check", tag)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    with open(os.path.join(d, "check_in.json"), "w") as f:
        json.dump({"sequences": [s for s in seqs if "ids" in s]}, f)
    log = os.path.join(launcher.state_dir(), "logs", f"{tag}.reference.log")
    rc = launcher.run_child(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "ref_child.py"),
         "--config", config_path, "--seed", str(seed), "--dir", d,
         "--cache-dir", cache_dir],
        launcher.child_env(platform, 1), log, timeout_s=600,
    )
    out_path = os.path.join(d, "check_out.json")
    if rc != 0 or not os.path.isfile(out_path):
        with open(log, errors="replace") as f:
            tail = "".join(f.readlines()[-30:])
        say(f"reference child failed (code {rc}):\n{tail}")
        return {}
    with open(out_path) as f:
        return json.load(f)


def judge(seqs: list, out: dict, limits: dict, say) -> bool:
    """Print every number compared beside its limit; True only if every
    sequence was answered and compared, and the run's rows are inside every
    limit."""
    ok = True
    for s in seqs:
        if "error" in s:
            say(f"check {s['name']}: NOT COMPARED: {s['error']}")
            ok = False
    rows = {r["name"]: r for r in out.get("sequences", [])}
    margins = []
    for s in seqs:
        if "ids" not in s:
            continue
        r = rows.get(s["name"])
        if r is None:
            say(f"check {s['name']}: NOT COMPARED: the reference gave no numbers")
            ok = False
            continue
        m = r["margins"]
        margins.extend(m)
        say(f"margins {s['name']}: {len(m)} generated rows after {r['n_prompt']} prompt tokens: "
            f"reference's top-1 chosen in {sum(x == 0 for x in m)}, mean margin "
            f"{sum(m) / len(m):.5f}, worst {max(m):.5f} logit-sigmas")
    if not margins:
        say("check: no row was compared")
        return False
    n = len(margins)
    numbers = {"mismatch": sum(x > 0 for x in margins) / n, "mean": sum(margins) / n,
               "worst": max(margins)}
    for name, value in numbers.items():
        good = value <= limits[name]
        ok &= good
        say(f"margins over {n} rows: {name} {value:.5f} (limit {limits[name]}) -> "
            f"{'ok' if good else 'FAIL'}")
    return bool(ok)
