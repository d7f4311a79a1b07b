#!/usr/bin/env python3
"""Cut a traced run down to a fixture for harness/program_scopes.py and
tools/scopes.py: the first chip's `XLA Modules` and `XLA Ops` events that
start inside `--seconds` of device activity, from `--start` seconds after
the first device event (`auto`: 1 ms before the first step-program
execution from which the cut holds a whole execution of every
`--step-module` given), and of the `program_scopes.json` beside the
profile the entries of the instructions that cut holds. Writes into
<out dir>: `cut.xplane.pb`, `program_scopes.json` and `expected.json`, the
last computed here by plain sorting and scanning, not by the code under test.

    python3 cellbench/tools/cut_scopes.py <trace dir> <out dir> --start auto --seconds 0.1 \
        --step-module mixed_step_ragged=1 --step-module decode_slots_paged=16
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

from jax.profiler import ProfileData

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cut_spans  # noqa: E402  (the fixture writer)

CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--start", default="auto")
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--step-module", action="append", required=True,
                    help="<part of the module's name>=<scheduler steps an execution makes>")
    args = ap.parse_args()
    steps_of = dict((h, int(n)) for h, n in (x.split("=") for x in args.step_module))
    src = next(os.path.join(b, f) for b, _, fs in os.walk(args.trace_dir)
               for f in sorted(fs) if f.endswith(".xplane.pb"))
    with open(os.path.join(args.trace_dir, "program_scopes.json")) as f:
        held = json.load(f)
    device = None
    for plane in ProfileData.from_file(src).planes:
        if re.match(r"^/device:TPU:\d+$", plane.name) and device is None:
            device = (plane.name, {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                for line in plane.lines if line.name in cut_spans.KEEP_LINES})
    pname, lines = device
    first = min(s for evs in lines.values() for _, s, _ in evs)
    if args.start == "auto":
        span = int(args.seconds * 1e9)
        runs = sorted((s, s + d, n) for n, s, d in lines["XLA Modules"]
                      if any(h in n for h in steps_of))
        t0 = next((s - 1_000_000 for s, _, _ in runs if all(
            any(h in n and s <= a and b < s - 1_000_000 + span for a, b, n in runs)
            for h in steps_of)), None)
        if t0 is None:
            raise SystemExit(f"no {args.seconds} s of the trace hold a whole execution "
                             f"of each of {list(steps_of)}")
    else:
        t0 = first + int(float(args.start) * 1e9)
    cut = t0 + int(args.seconds * 1e9)
    lines = {k: [x for x in evs if t0 <= x[1] < cut] for k, evs in lines.items()}
    text = cut_spans.xspace_text(pname, lines, [], t0)
    # ---- expected, by plain sorting and scanning
    mods = sorted((s, s + d, re.sub(r"\(\d+\)$", "", n).strip())
                  for n, s, d in lines["XLA Modules"] if any(h in n for h in steps_of))
    modules, used, steps = {}, {}, 0
    for s, e, name in mods:
        m = modules.setdefault(name, {"executions": 0, "seconds": 0.0, "by_scope": {},
                                      "mixed_s": 0.0, "unknown_s": 0.0})
        m["executions"] += 1
        steps += next(n for h, n in steps_of.items() if h in name)
    for n, s, d in lines["XLA Ops"]:
        inside = [m for m in mods if m[0] <= s < m[1]]
        inst = n.split(" = ", 1)[0].strip()
        if not inside or CONTAINER.match(inst):
            continue
        name = inside[0][2]
        m = modules[name]
        m["seconds"] += d * 1e-9
        entry = held["programs"].get(name, {}).get(inst)
        if entry is None:
            m["unknown_s"] += d * 1e-9
            continue
        used.setdefault(name, {})[inst] = entry
        key = "/".join(entry["scope"])
        m["by_scope"][key] = m["by_scope"].get(key, 0.0) + d * 1e-9
        if entry["mixed"] > 1:
            m["mixed_s"] += d * 1e-9

    def under(labels):
        return sum(v for m in modules.values() for k, v in m["by_scope"].items()
                   if k.split("/")[0] in labels)

    total = sum(m["seconds"] for m in modules.values())
    expected = {
        "source": {"start_s_after_first_event": (t0 - first) * 1e-9, "seconds": args.seconds},
        "events": {k: len(v) for k, v in lines.items()}, "steps": steps, "modules": modules,
        "metrics": {
            "scoped_device_pct": 100.0 * sum(
                v for m in modules.values() for k, v in m["by_scope"].items() if k) / total,
            "attn_layer_ms_per_step": 1e3 * under(("attn",)) / steps,
            "ffn_ms_per_step": 1e3 * under(("ffn",)) / steps,
            "moe_layer_ms_per_step": 1e3 * under(
                ("moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared")) / steps,
            "conv_mix_ms_per_step": 1e3 * under(("conv_mix",)) / steps,
            "head_sample_ms_per_step": 1e3 * under(("head", "sample")) / steps,
        },
    }
    os.makedirs(args.out, exist_ok=True)
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with open(os.path.join(args.out, "cut.xplane.pb"), "wb") as f:
        f.write(blob)
    with open(os.path.join(args.out, "program_scopes.json"), "w") as f:
        json.dump({"vocabulary": held["vocabulary"], "programs": used}, f, indent=0)
    with open(os.path.join(args.out, "expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(f"{args.out}: cut.xplane.pb {len(blob)} bytes, {expected['events']} events, "
          f"{sum(map(len, used.values()))} instructions of the map, {steps} steps; "
          f"metrics {json.dumps(expected['metrics'])}")


if __name__ == "__main__":
    main()
