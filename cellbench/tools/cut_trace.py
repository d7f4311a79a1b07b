#!/usr/bin/env python3
"""Cut a recorded trace down to a fixture a hand can check: the device
planes' `XLA Modules` and `XLA Ops` events that start inside the first
`--seconds` of device activity, with their names, starts and durations as
recorded. Writes <out>.xplane.pb and <out>.expected.json, the latter
computed here by plain sorting and summing (not by harness/trace_reduce).

    python3 cellbench/tools/cut_trace.py <in.xplane.pb> <out prefix> --seconds 0.08
"""

from __future__ import annotations

import argparse
import json
import re

from jax.profiler import ProfileData

KEEP_LINES = ("XLA Modules", "XLA Ops")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.08)
    args = ap.parse_args()
    data = ProfileData.from_file(args.src)
    planes = []
    for plane in data.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in KEEP_LINES:
                lines[line.name] = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
        if lines:
            planes.append((plane.name, lines))
    t0 = min(s for _, ls in planes for evs in ls.values() for _, s, _ in evs)
    cut = t0 + int(args.seconds * 1e9)
    text, expected = [], None
    for pi, (pname, lines) in enumerate(planes):
        names = sorted({n for evs in lines.values() for n, s, _ in evs if s < cut})
        ids = {n: i + 1 for i, n in enumerate(names)}
        text.append(f'planes {{ id: {pi + 1} name: "{pname}"')
        for li, (lname, evs) in enumerate(lines.items()):
            text.append(f'  lines {{ id: {li + 1} name: "{lname}" timestamp_ns: {t0}')
            for n, s, d in evs:
                if s < cut:
                    text.append(f"    events {{ metadata_id: {ids[n]} offset_ps: {(s - t0) * 1000} "
                                f"duration_ps: {d * 1000} }}")
            text.append("  }")
        for n, i in ids.items():
            esc = n.replace("\\", "\\\\").replace('"', '\\"')
            text.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{esc}" }} }}')
        text.append("}")
        if pi == 0:  # the expected numbers, for one chip, by sorting and summing
            ops = sorted((s, s + d, n) for n, s, d in lines.get("XLA Ops", []) if s < cut)
            mods = sorted((s, s + d, n) for n, s, d in lines.get("XLA Modules", []) if s < cut)
            busy, end = 0, None
            for s, e, _ in ops:
                if end is None or s > end:
                    busy += e - s
                    end = e
                elif e > end:
                    busy += e - end
                    end = e
            lo = min(x[0] for x in ops + mods)
            hi = max(x[1] for x in ops + mods)
            modules, optot = {}, {}
            for s, e, n in mods:
                modules.setdefault(re.sub(r"\(\d+\)$", "", n).strip(), []).append((e - s) * 1e-9)
            for s, e, n in ops:
                short = n.split(" = ", 1)[0].strip()  # the instruction's own name
                if not re.match(r"^%?(while|conditional|call)[.\d]*$", short):  # containers
                    optot[short] = optot.get(short, 0.0) + (e - s) * 1e-9
            expected = {"chips": len(planes), "window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
                        "idle_share": 1 - busy / (hi - lo), "modules": modules,
                        "ops": dict(sorted(optot.items(), key=lambda kv: -kv[1])[:8]),
                        "events": {"ops": len(ops), "modules": len(mods)}}
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with open(args.out + ".xplane.pb", "wb") as f:
        f.write(blob)
    with open(args.out + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(f"{args.out}.xplane.pb: {len(blob)} bytes; expected: "
          f"{json.dumps({k: v for k, v in expected.items() if k not in ('modules', 'ops')})}")


if __name__ == "__main__":
    main()
