#!/usr/bin/env python3
"""Cut a recorded trace down to a fixture for harness/host_spans.py and
tools/gaps.py: the first chip's `XLA Modules` and `XLA Ops` events and the
worker's spans (`phase.*`, `launch.*`, `fetch.*` and the `begin.` markers,
with their stats) that start inside `--seconds` of device activity from
`--start` on. Writes
<out>.xplane.pb and <out>.expected.json; the expected numbers are computed
here by plain sorting and scanning, not by the code under test: for each
launch whose fetch is in the cut, the step module that was the last to end
before that fetch returned (right while the chip sets the pace, as in the
recorded run),
and for each idle gap the span whose interval holds the gap's start.

    python3 cellbench/tools/cut_spans.py <in.xplane.pb> <out prefix> --start 2.2 --seconds 0.3 \
        --step-module mixed_step_ragged --step-module decode_slots_paged
"""

from __future__ import annotations

import argparse
import json
import re

from jax.profiler import ProfileData

KEEP_LINES = ("XLA Modules", "XLA Ops")
PREFIXES = ("phase.", "launch.", "fetch.", "begin.")


def esc(text) -> str:
    return str(text).replace("\\", "\\\\").replace('"', '\\"')


def xspace_text(pname: str, lines: dict, spans: list, base: int) -> list:
    """Lines of an XSpace text proto: one device plane `pname` with `lines`
    {line name: [(event name, start_ns, duration_ns)]} and a `/host:CPU`
    plane with one line of `spans` [(name, start_ns, duration_ns, stats)],
    all offsets from `base`."""
    text = [f'planes {{ id: 1 name: "{pname}"']
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    for li, (lname, evs) in enumerate(lines.items()):
        text.append(f'  lines {{ id: {li + 1} name: "{lname}" timestamp_ns: {base}')
        for n, s, d in evs:
            text.append(f"    events {{ metadata_id: {ids[n]} offset_ps: {(s - base) * 1000} "
                        f"duration_ps: {d * 1000} }}")
        text.append("  }")
    for n, i in ids.items():
        text.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{esc(n)}" }} }}')
    text.append("}")
    # the worker's line: one event metadata per span name, one stat metadata per key
    snames = sorted({n for n, *_ in spans})
    sids = {n: i + 1 for i, n in enumerate(snames)}
    keys = sorted({k for *_, st in spans for k in st})
    kids = {k: i + 1 for i, k in enumerate(keys)}
    text.append('planes { id: 2 name: "/host:CPU"')
    text.append(f'  lines {{ id: 1 name: "continuous-engine" timestamp_ns: {base}')
    for n, s, d, st in spans:
        stats = " ".join(
            f'stats {{ metadata_id: {kids[k]} ' + (
                f"int64_value: {int(v)}" if isinstance(v, int) and not isinstance(v, bool)
                else f'str_value: "{esc(v)}"') + " }"
            for k, v in st.items())
        text.append(f"    events {{ metadata_id: {sids[n]} offset_ps: {(s - base) * 1000} "
                    f"duration_ps: {d * 1000} {stats} }}")
    text.append("  }")
    for n, i in sids.items():
        text.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{esc(n)}" }} }}')
    for k, i in kids.items():
        text.append(f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{esc(k)}" }} }}')
    text.append("}")
    return text


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--start", type=float, default=0.0,
                    help="seconds after the first device event at which the cut begins")
    ap.add_argument("--step-module", action="append", required=True)
    ap.add_argument("--min-us", type=float, default=50.0)
    args = ap.parse_args()
    device, spans = None, []
    for plane in ProfileData.from_file(args.src).planes:
        if re.match(r"^/device:TPU:\d+$", plane.name) and device is None:
            device = (plane.name, {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                for line in plane.lines if line.name in KEEP_LINES})
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append((e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats)))
    pname, lines = device
    t0 = min(s for evs in lines.values() for _, s, _ in evs) + int(args.start * 1e9)
    cut = t0 + int(args.seconds * 1e9)
    lines = {k: [x for x in evs if t0 <= x[1] < cut] for k, evs in lines.items()}
    spans = sorted((x for x in spans if t0 <= x[1] < cut), key=lambda x: x[1])
    base = min(t0, spans[0][1]) if spans else t0
    text = xspace_text(pname, lines, spans, base)
    # ---- expected, by plain sorting and scanning
    mods = sorted((s, s + d, n) for n, s, d in lines.get("XLA Modules", [])
                  if any(h in n for h in args.step_module))
    fetch_end = {int(st["seq"]): s + d for n, s, d, st in spans if n.startswith("fetch.")}
    joined = []
    for n, s, d, st in spans:
        if n.startswith("launch.") and int(st["seq"]) in fetch_end:
            done = [m for m in mods if m[1] <= fetch_end[int(st["seq"])]]
            if done:
                m = max(done, key=lambda m: m[1])
                joined.append({"seq": int(st["seq"]), "kv_tokens": int(st["kv_tokens"]),
                               "module_start_s": m[0] * 1e-9, "module_end_s": m[1] * 1e-9,
                               "module": re.sub(r"\(\d+\)$", "", m[2])})
    ops = sorted((s, s + d) for _, s, d in lines.get("XLA Ops", []))
    every = [(s, s + d) for evs in lines.values() for _, s, d in evs]
    lo, hi = min(s for s, _ in every), max(e for _, e in every)
    gaps, end = [], lo
    for s, e in ops:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if end < hi:
        gaps.append((end, hi))
    markers = [x for x in spans if x[0].startswith("begin.")]
    spans = [x for x in spans if not x[0].startswith("begin.")]
    ends = []  # the intervals open when the profiler started and stopped
    if spans and spans[0][1] > lo and spans[0][3].get("prev"):
        ends.append(("phase." + spans[0][3]["prev"], lo, spans[0][1] - lo, {}))
    if markers and spans and markers[-1][1] >= spans[-1][1] + spans[-1][2] and markers[-1][1] < hi:
        ends.append((markers[-1][0][len("begin."):], markers[-1][1], hi - markers[-1][1], {}))
    owners, owned, idle = [], 0, 0
    for gs, ge in gaps:
        idle += ge - gs
        who = [n for n, s, d, _ in ends + spans if s <= gs < s + d]
        if who:
            owned += ge - gs
        if (ge - gs) >= args.min_us * 1e3:
            owners.append({"start_ms": (gs - lo) * 1e-6, "us": (ge - gs) * 1e-3,
                           "span": who[0] if who else None})
    expected = {
        "spans": len(spans), "markers": len(markers),
        "span_names": sorted({n for n, *_ in spans}),
        "first_span": {"name": spans[0][0], "start_s": spans[0][1] * 1e-9,
                       "stats": {k: v for k, v in spans[0][3].items()}} if spans else None,
        "joined": joined, "window_s": (hi - lo) * 1e-9, "idle_s": idle * 1e-9,
        "idle_owned_pct": 100.0 * owned / idle if idle else None, "gaps": owners,
    }
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with open(args.out + ".xplane.pb", "wb") as f:
        f.write(blob)
    with open(args.out + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(f"{args.out}.xplane.pb: {len(blob)} bytes, {len(spans)} spans, {len(joined)} launches "
          f"joined, {len(owners)} gaps of {args.min_us:g} us or more")


if __name__ == "__main__":
    main()
