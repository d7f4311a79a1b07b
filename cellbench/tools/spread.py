#!/usr/bin/env python3
"""Run one cell several times and print how widely its metrics spread:
the measurement behind the bounds in BENCHMARK.json (PERF.md, section 2).

    python3 cellbench/tools/spread.py --workload olmo2-chat --seeds 11,12,13,14,15,16 \
        --sets 2 [--seconds 40] [--trace 0] [--out chiprun_out/spread]

Each set runs the same seeds. A spread is the distance between the first
and third quartile (statistics.quantiles(values, n=4)) as a share of the
median; the first run of the call compiles and is shown apart."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "spread"))
    ap.add_argument("extra", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seconds = args.seconds or manifest["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(args.out, exist_ok=True)
    extra = args.extra[1:] if args.extra[:1] == ["--"] else args.extra
    rows = []
    for s in range(args.sets):
        for seed in seeds:
            cmd = manifest["command"] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", f"{seconds:g}", "--trace", str(args.trace)] + extra
            t0 = time.time()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            took = time.time() - t0
            log = os.path.join(args.out, f"{args.workload}.set{s}.seed{seed}.trace{args.trace}.log")
            with open(log, "w") as f:
                f.write(p.stdout + "\n--- stderr ---\n" + p.stderr[-4000:])
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            try:
                res = json.loads(last) if p.returncode == 0 else None
            except ValueError:
                res = None
            row = {"set": s, "seed": seed, "rc": p.returncode, "took_s": round(took, 1),
                   "result": res}
            rows.append(row)
            vals = {k: round(v["value"], 4) for k, v in (res or {}).get("metrics", {}).items()}
            print(f"set {s} seed {seed}: rc {p.returncode} in {took:.0f} s, correct "
                  f"{(res or {}).get('correct')}, attempted {(res or {}).get('attempted')}, "
                  f"failed {(res or {}).get('failed')}, {vals}", flush=True)
            if res is None:
                print("    last lines:\n    " + "\n    ".join(p.stdout.strip().splitlines()[-6:]), flush=True)
    with open(os.path.join(args.out, f"{args.workload}.trace{args.trace}.jsonl"), "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    good = [r for r in rows if r["result"]]
    names = sorted({k for r in good for k in r["result"]["metrics"]})
    for name in names:
        line = [name]
        for s in range(args.sets):
            vals = [r["result"]["metrics"][name]["value"] for r in good
                    if r["set"] == s and name in r["result"]["metrics"]]
            if name == "setup_s" and s == 0:
                vals = vals[1:]  # the call's first run compiles
            if len(vals) >= 2:
                line.append(f"set {s}: median {statistics.median(vals):.4f} "
                            f"spread {100 * spread(vals):.2f}% (n {len(vals)})")
        print(" | ".join(line), flush=True)
    return 0 if len(good) == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
