"""The host's half of a profiler trace: the spans the program's worker thread
writes on the profiler's own clock, beside the device's events.

`harness/trace_reduce.py` reads the device planes only. The continuous
engine's worker marks every interval of its loop with one
`jax.profiler.TraceAnnotation` (utils/tracing.PhaseClock): `phase.<name>`
(wait_work, reap, admit, plan, distribute), `launch.<mixed|chunk>` around a
dispatch, carrying the launch record as the event's stats (seq, steps,
decode_rows, prefill_tokens, steps_ahead, kv_tokens, kv_grid_tokens, ...),
and `fetch.<mixed|chunk>` around the blocking fetch, carrying `seq`. They
land on one line of the `/host:CPU` plane (the worker thread's, among the
Python tracer's function events), in the units and from the origin of the
device planes' events (read by hand, PERF.md: a fetch ends 0.3-3 ms after
the module it waited for).

The intervals are contiguous, so a span is open at every instant between
the first and the last recorded one. The profiler keeps no interval that
was open when it started or stopped. Every span says which one it followed
(`prev`), so the head is put back from the first one recorded; and the two
phases in which the worker blocks (`wait_work`, `fetch_wait`) are preceded
by an instant marker `begin.<span name>` with the span's stats, so the
tail, a wait that outlasted the profiler, runs from the last marker to the
end (`bounded`).

A program without these spans (an older commit) gives an empty list, and
every reader built on this returns None.
"""

from __future__ import annotations

import bisect
import os

PREFIXES = ("phase.", "launch.", "fetch.")
MARKER = "begin."
HOST_PLANE = "/host:"


def find(trace_dir):
    """The first .xplane.pb under a trace directory, or None."""
    for base, _, files in os.walk(trace_dir or ""):
        for f in sorted(files):
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    return None


def read(path: str) -> list:
    """[(name, start_s, end_s, stats)] of the worker's spans and `begin.`
    markers, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES + (MARKER,)):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats)))
    return sorted(out, key=lambda x: x[1])


def bounded(spans: list, lo: float, hi: float) -> list:
    """The spans without their markers, with the two intervals the profiler
    dropped put back: the one open when it started, from `lo` (the start of
    the device's window) to the first recorded span, named by that span's
    `prev`; and the wait open when it stopped, from the last marker (if no
    span was recorded after it) to `hi`, under the marker's name and stats."""
    real = [s for s in spans if not s[0].startswith(MARKER)]
    if not real:
        return real
    prev = real[0][3].get("prev")
    if prev and real[0][1] > lo:
        real = [(f"phase.{prev}", lo, real[0][1], {"head": 1})] + real
    markers = [s for s in spans if s[0].startswith(MARKER)]
    if markers and markers[-1][1] >= real[-1][2] - 1e-6 and markers[-1][1] < hi:
        name, start, _, stats = markers[-1]
        real.append((name[len(MARKER):], start, hi, {**stats, "tail": 1}))
    return real


def open_at(spans: list, t: float, starts=None):
    """The span open at time t, or None (before the first, after the last,
    or in a hole). `starts`: the spans' start times, where the caller asks
    often and keeps them."""
    i = bisect.bisect_right(starts or [s[1] for s in spans], t) - 1
    if i >= 0 and t < spans[i][2]:
        return spans[i]
    return None


def covered(spans: list, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside some span (they do not overlap)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for _, s, e, _ in spans)


def join_launches(spans: list, modules: list, step_modules: dict) -> list:
    """[(launch stats, module start_s, module end_s)]: each `launch.*` span
    with the execution of its step program on the first chip.

    `modules` are that chip's `XLA Modules` events (name, start_s, end_s);
    `step_modules` the configuration's `serving.trace.step_modules`. The
    device runs launches in the order they were dispatched, so launch i is
    the (i + h)-th step module for one h: the modules at the head were
    dispatched before the profiler started and have no span, the launches
    at the tail ran after it stopped. h is fixed by what must hold for every
    pair: a module starts after its dispatch began, and ends before the
    fetch of that launch returned. While the chip sets the pace a fetch
    returns just as its module ends, so the largest h the fetches allow is
    the answer; where launch and module kinds then disagree (a mixed step
    against a chunk), the h in the allowed range with fewest disagreements.
    Only launches whose fetch returned inside the trace are given back: the
    device trace cuts the module that runs when it ends, and such a module
    would be paired with a whole launch's work."""
    mods = sorted((m for m in modules if any(h in m[0] for h in step_modules)),
                  key=lambda m: m[1])
    launches = sorted((s for s in spans if s[0].startswith("launch.") and "seq" in s[3]),
                      key=lambda s: int(s[3]["seq"]))
    if not mods or not launches:
        return []
    fetch_end = {int(s[3]["seq"]): s[2] for s in spans if s[0].startswith("fetch.") and "seq" in s[3]}
    eps = min(0.005, 0.5 * min(e - s for _, s, e in mods))
    starts, ends = [m[1] for m in mods], [m[2] for m in mods]
    seq0 = int(launches[0][3]["seq"])
    place = [int(ln[3]["seq"]) - seq0 for ln in launches]  # seqs are consecutive
    lo, hi = -place[-1], len(mods)
    for i, ln in zip(place, launches):
        lo = max(lo, bisect.bisect_left(starts, ln[1] - eps) - i)
        if i + seq0 in fetch_end:
            hi = min(hi, bisect.bisect_right(ends, fetch_end[i + seq0] + eps) - 1 - i)
    if hi < lo:
        lo = hi

    def kind(module_name):
        steps = next(v for h, v in step_modules.items() if h in module_name)
        return "launch.mixed" if steps == 1 else "launch.chunk"

    def pairs(h):
        return [(ln, mods[i + h]) for i, ln in zip(place, launches) if 0 <= i + h < len(mods)]

    best = max(range(lo, hi + 1),
               key=lambda h: (-sum(ln[0] != kind(m[0]) for ln, m in pairs(h)), h))
    return [(ln[3], m[1], m[2]) for ln, m in pairs(best) if int(ln[3]["seq"]) in fetch_end]
