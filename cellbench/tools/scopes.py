#!/usr/bin/env python3
"""Where a step program's device time goes, by the program's own scopes:
per step program of a trace its executions and their median, then the
milliseconds an execution under each scope, then the ten largest
instructions under no scope and the ten largest fusions of more than one
scope's work (harness/program_scopes.py; the trace directory holds the
`program_scopes.json` the program wrote when the profiler session ended).

    python3 cellbench/tools/scopes.py <trace dir> [--module-hint mixed_step_ragged ...] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import host_spans, program_scopes, trace_reduce  # noqa: E402

STEP_MODULES = ("mixed_step_ragged", "decode_slots_paged")


def table(trace_dir: str, hints=STEP_MODULES) -> dict:
    held = program_scopes.load(trace_dir)
    path = host_spans.find(trace_dir)
    if held is None:
        raise SystemExit(f"no {program_scopes.FILE} in {trace_dir}: the program wrote none")
    if path is None:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    planes = trace_reduce.read_planes(path)
    if not planes:
        raise SystemExit("the trace holds no device plane")
    got = program_scopes.attribute(planes[min(planes)], held["programs"], hints)
    out = {"trace": path, "unmapped_modules": got["unmapped_modules"], "programs": {}}
    for name, mod in got["modules"].items():
        n = len(mod["executions"])

        def top(d):
            return [[k, 1e3 * v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

        out["programs"][name] = {
            "executions": n,
            "median_ms": 1e3 * sorted(mod["executions"])[n // 2],
            "device_ms": 1e3 * mod["seconds"] / n,
            "ms_by_scope": {k or "(no scope)": 1e3 * v / n for k, v in
                            sorted(mod["by_scope"].items(), key=lambda kv: -kv[1])},
            "unknown_ms": 1e3 * sum(mod["unknown"].values()) / n,
            "mixed_ms": 1e3 * sum(mod["mixed"].values()) / n,
            "largest_unlabelled": top(mod["unlabelled"]),
            "largest_mixed": top(mod["mixed"]),
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--module-hint", action="append",
                    help=f"a part of a step program's module name (default {list(STEP_MODULES)})")
    ap.add_argument("--json", action="store_true", help="the whole table as one JSON object")
    args = ap.parse_args()
    r = table(args.trace_dir, tuple(args.module_hint or STEP_MODULES))
    if args.json:
        print(json.dumps(r))
        return
    print(r["trace"])
    for name in r["unmapped_modules"]:
        print(f"{name}: not in the map (the session never dispatched it)")
    for name, p in sorted(r["programs"].items(), key=lambda kv: -kv[1]["device_ms"] * kv[1]["executions"]):
        print(f"{name}: {p['executions']} executions, median {p['median_ms']:.3f} ms, "
              f"operations {p['device_ms']:.3f} ms an execution "
              f"(in fusions of several scopes {p['mixed_ms']:.3f}, unknown to the map {p['unknown_ms']:.3f})")
        for scope, ms in p["ms_by_scope"].items():
            print(f"  {ms:9.4f} ms  {100 * ms / p['device_ms']:5.1f}%  {scope}")
        for title, rows in (("largest under no scope", p["largest_unlabelled"]),
                            ("largest fusions of several scopes", p["largest_mixed"])):
            if rows:
                print(f"  {title} (ms an execution):")
            for inst, ms in rows:
                print(f"    {ms:9.4f}  {inst}")


if __name__ == "__main__":
    main()
