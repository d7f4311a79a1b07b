"""What every traffic generator shares: the word vocabulary that makes a
prompt's token count exact, and the fixed trace of sizes and gaps that every
seed replays from another starting point. NOTHING HERE IS SAMPLED FROM THE
SEED but the words: an "open loop, Poisson" mix is a fixed stratified set of
exponential gaps, not a fresh draw of a Poisson process, so the number of
arrivals in a window and the multiset of their sizes never vary, and bursts
are only those the one fixed order holds (PERF.md section 4 says what that
leaves out, and what a fresh order per seed did to the spreads). A mix may
pin even the starting point (`begin_at`, generators/sessions.py): then every
seed replays the same trace.

Prompts are strings of words `w<id>`; the benchmark's word-level tokenizer
(harness/tokenizer.py) maps each word to the one token <id>, so a prompt of
n words is n tokens whatever the model's vocabulary, a shared prefix is a
shared list of words, and every generated token renders as a word (the
offline byte tokenizer renders ids above 258 as nothing, so a stream would
carry no event until the request's end)."""

from __future__ import annotations

import dataclasses
import math
import random
import statistics

N_SPECIAL = 3  # ids 0, 1, 2 are <pad>, <s>, </s> in the word tokenizer


@dataclasses.dataclass
class Request:
    prompt: str
    n_prompt: int
    max_tokens: int
    shared_tokens: int = 0  # leading tokens an earlier request of the session sent
    greedy: bool = True
    temperature: float = 1.0
    fields: dict = dataclasses.field(default_factory=dict)  # further body fields


@dataclasses.dataclass
class Session:
    due_s: float  # offset from the start of the ramp
    turns: list  # [Request]
    think_s: list  # think_s[i]: gap between the end of turn i and turn i+1 being due


class Words:
    """The usable word ids of one model: everything but the specials and
    the model's stop tokens (a prompt must not end a request by accident)."""

    def __init__(self, vocab_size: int, stop_ids=()):
        self.vocab_size = int(vocab_size)
        self._skip = sorted(i for i in set(stop_ids) if i >= N_SPECIAL)
        self._n = self.vocab_size - N_SPECIAL - len(self._skip)

    def ids(self, rng: random.Random, n: int) -> list:
        out = []
        for _ in range(n):
            i = N_SPECIAL + rng.randrange(self._n)
            for s in self._skip:  # step over the holes, in order
                if i >= s:
                    i += 1
            out.append(i)
        return out

    @staticmethod
    def text(ids) -> str:
        return " ".join(f"w{i}" for i in ids)


def _ppf(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "fixed":
        x = float(dist["value"])
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "exponential":
        x = -math.log1p(-u) * dist["mean"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        x = max(x, dist["min"])
    if "max" in dist:
        x = min(x, dist["max"])
    return x


def stratified(dist: dict, n: int, order: str, shift: int = 0, integer: bool = False,
               group: int = 1) -> list:
    """n draws that are the distribution's n mid-quantiles, in an order fixed
    by the mix (a shuffle keyed by `order`, the same for every seed) and
    rotated by `shift` groups of `group` (the seed's part): every seed gets
    the same multiset in the same cyclic order, begun at another place. A
    plain sample would let the seed change the work, and a fresh shuffle per
    seed moved a window's median latency by 5-8% (PERF.md, PR 23)."""
    xs = [_ppf(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        xs = [int(round(x)) for x in xs]
    random.Random(order).shuffle(xs)
    k = (shift * group) % n if n else 0
    return xs[k:] + xs[:k]


def arrivals(rate: float, length_s: float, order: str, shift: int = 0) -> list:
    """round(rate x length) due times inside [0, length): the n mid-quantiles
    of the exponential distribution as gaps, in the mix's fixed order,
    rotated by the seed and scaled to fill the part exactly. The gaps have a
    Poisson process's distribution (CV 1), but the count is the same for
    every seed and so is the sequence, up to where it begins: this is one
    trace replayed, not a process sampled."""
    n = int(round(rate * length_s))
    if n <= 0:
        return []
    gaps = stratified({"dist": "exponential", "mean": 1.0}, n, order, shift)
    gaps.append(1.0)  # the gap that ends the part: a mean one, under every seed
    scale = length_s / sum(gaps)
    t, out = 0.0, []
    for g in gaps[:n]:
        t += g * scale
        out.append(t)
    return out


def sampling(traffic: dict) -> dict:
    s = traffic.get("sampling", {})
    return {"greedy": bool(s.get("greedy", True)),
            "temperature": float(s.get("temperature", 1.0)),
            "fields": dict(traffic.get("request_fields", {}))}
