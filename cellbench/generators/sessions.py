"""The one general traffic generator: sessions of turns over an optional
shared document, arriving in an open loop (exponential gaps) or driven by a
fixed fleet of clients in a closed loop. Every mix the benchmark has is a data
file of parameters for it (cellbench/traffic/*.json):

    {"generator": "sessions",
     "load": {"loop": "open", "rate": <sessions/s>}            # or
             {"loop": "closed", "clients": <n>},
     "session": {"turns": 4, "think_s": {"dist": "exponential", "mean": 1.0},
                 "doc_tokens": {"dist": "lognormal", ...}},    # optional
     "prompt_tokens": {...},      # the fresh part of every turn's prompt
     "max_tokens": {...},
     "sampling": {"greedy": true}}

A turn's prompt is the session's document (if any) followed by the fresh
tokens; turn k > 0 is due `think_s` after the end of turn k-1. Sizes and
gaps are fixed multisets in a cyclic order fixed by the mix; the seed
chooses where the cycle begins and draws the words
(harness/traffic_lib.stratified), so every seed offers the same work in
another order. A mix that gives `"begin_at": <k>` begins the cycle at place
k under every seed: then the seed draws the words alone, and every run
replays the same arrivals and sizes in the same order (docs-repeat, whose
windows begun elsewhere differ by more than a bound can hold: PERF.md).
"""

from __future__ import annotations

import random

from harness.traffic_lib import Request, Session, Words, arrivals, sampling, stratified


def plan_open(traffic: dict, load: dict, seed: int, segments, words: Words) -> list:
    """[Session] sorted by due time. `segments` are the lengths in seconds of
    the run's parts (ramp, window, tail): each part gets round(rate x length)
    arrivals and its own multiset of sizes, so the measured window holds the
    same number of sessions and the same sizes under every seed."""
    if isinstance(segments, (int, float)):
        segments = [float(segments)]
    sess = traffic.get("session", {})
    turns = int(sess.get("turns", 1))
    samp = sampling(traffic)
    shift = int(traffic.get("begin_at", seed))  # where the fixed cycle begins
    out, start = [], 0.0
    for k, length in enumerate(segments):
        rng = random.Random(f"{seed}:open:{k}")  # the words only
        order = f"0:{k}:"  # keys the fixed shuffle: the part of the run, never the seed
        due = [start + t for t in arrivals(float(load["rate"]), length, order + "gaps", shift)]
        start += length
        n = len(due)
        if n == 0:
            continue

        def draw(dist, what, per=1, integer=True):
            return stratified(dist, n * per, order + what, shift, integer, group=per)

        docs = draw(sess["doc_tokens"], "docs") if sess.get("doc_tokens") else [0] * n
        fresh = draw(traffic["prompt_tokens"], "fresh", turns)
        outs = draw(traffic["max_tokens"], "outs", turns)
        think = (draw(sess["think_s"], "think", turns, integer=False) if turns > 1
                 else [0.0] * (n * turns))
        for i, t in enumerate(due):
            doc = words.ids(rng, docs[i])
            reqs = []
            for j in range(i * turns, (i + 1) * turns):
                ids = doc + words.ids(rng, fresh[j])
                reqs.append(Request(
                    prompt=Words.text(ids), n_prompt=len(ids), max_tokens=outs[j],
                    shared_tokens=len(doc) if j > i * turns else 0, **samp,
                ))
            out.append(Session(due_s=t, turns=reqs, think_s=think[i * turns:(i + 1) * turns]))
    return out


class ClosedPlan:
    """Closed loop: `clients` callers, each sending its next request when
    the previous one is answered. Request k of client c is a function of
    (seed, c, k), so the fleet's work does not depend on who finishes first;
    sizes cycle through one stratified multiset per client."""

    def __init__(self, traffic: dict, load: dict, seed: int, words: Words, cycle: int = 16):
        self.clients = int(load["clients"])
        self._seed, self._words, self._samp = seed, words, sampling(traffic)
        n = self.clients * cycle
        self._cycle = cycle
        order = "0:closed:"
        self._fresh = stratified(traffic["prompt_tokens"], n, order + "fresh", seed, True)
        self._outs = stratified(traffic["max_tokens"], n, order + "outs", seed, True)

    def request(self, client: int, k: int) -> Request:
        j = client * self._cycle + k % self._cycle
        rng = random.Random(f"{self._seed}:closed:{client}:{k}")
        ids = self._words.ids(rng, self._fresh[j])
        return Request(
            prompt=Words.text(ids), n_prompt=len(ids), max_tokens=self._outs[j],
            **self._samp,
        )
