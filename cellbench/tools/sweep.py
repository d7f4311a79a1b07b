#!/usr/bin/env python3
"""Find an open-loop cell's knee, once: the offered rate rises by x1.25 per
step in one server start; the knee is the highest step at which at least 98%
of the requests due completed and no more requests were in flight at the
step's end than at its start. The cell's rate is 0.8 x knee (PERF.md).

    python3 cellbench/tools/sweep.py --workload olmo2-chat --seed 7 --start 2 --steps 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import launcher, stats, tokenizer  # noqa: E402
from harness.load import Fleet, run_open  # noqa: E402
from harness.manifest import ROOT, Cell, load_json, load_module  # noqa: E402
from harness.traffic_lib import Words  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--start", type=float, required=True, help="first rate")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--step-s", type=float, default=20.0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    cell = Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    config, traffic = cell.config, cell.traffic
    words = Words(config["vocab_size"], config.get("stop_token_ids", ()))
    tok_dir = tokenizer.ensure(launcher.state_dir(), config["vocab_size"])
    gen = load_module("generators", traffic["generator"])
    rates = [args.start * args.factor ** i for i in range(args.steps)]
    with launcher.Server(cell.config_path, config, args.seed, args.platform, tok_dir,
                         f"sweep.{cell.name}") as server:
        print(f"server ready in {server.ready_s:.1f} s on {server.device()}", flush=True)
        fleet = Fleet("127.0.0.1", server.port)
        t0 = time.monotonic() + 0.5
        marks = []
        for i, rate in enumerate(rates):
            sessions = gen.plan_open(traffic, {**cell.load, "rate": rate},
                                     args.seed + i, args.step_s, words)
            run_open(fleet, sessions, t0 + i * args.step_s)
        for i in range(len(rates) + 1):
            time.sleep(max(0.0, t0 + i * args.step_s - time.monotonic()))
            q = server.get("/stats").get("continuous", {})
            marks.append({"inflight": fleet.inflight(), "queued": q.get("queued"),
                          "occupied": q.get("occupied"),
                          "free_blocks": (q.get("paged") or {}).get("free_blocks")})
        fleet.offer_until = time.monotonic()
        time.sleep(15.0)  # let the last steps' requests finish or not
        fleet.cancel_open()
        fleet.join(30)
    print("step rate due ok share% ttft_p50 ttft_p95 tpot_p50 inflight(start->end) "
          "queued(start->end) free_blocks(end)")
    table = []
    for i, rate in enumerate(rates):
        lo, hi = t0 + i * args.step_s, t0 + (i + 1) * args.step_s
        due = [r for r in fleet.results if lo <= r.due < hi]
        ok = [r for r in due if r.ok]
        ttft = [(r.first - r.due) * 1e3 for r in ok]
        tpot = [(r.done - r.first) * 1e3 / (r.tokens - 1) for r in ok if r.tokens > 1]
        p = lambda xs, q: stats.percentile(xs, q, enforce=False) if xs else float("nan")  # noqa: E731
        row = {"step": i, "rate": round(rate, 3), "due": len(due), "ok": len(ok),
               "share": round(100.0 * len(ok) / max(1, len(due)), 1),
               "ttft_p50": round(p(ttft, 50), 1), "ttft_p95": round(p(ttft, 95), 1),
               "tpot_p50": round(p(tpot, 50), 2),
               "inflight": [marks[i]["inflight"], marks[i + 1]["inflight"]],
               "queued": [marks[i]["queued"], marks[i + 1]["queued"]],
               "free_blocks": marks[i + 1]["free_blocks"]}
        table.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{cell.name}.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
