#!/usr/bin/env python3
"""Who owns the chip's idle time: every gap between device operations of the
first chip that is longer than --min-us, with the worker thread's span that
was open when the gap began (harness/host_spans.py: `phase.*`, `launch.*`,
`fetch.*`; the intervals open when the profiler started and stopped put
back from `prev` and the last `begin.` marker).

    python3 cellbench/tools/gaps.py <file.xplane.pb | trace dir> [--min-us 50] [--top 40]

Ends with the sums: idle time by span, the share of the idle time that has
an owner, and the share of the traced window the worker's spans cover. A
gap after the last span (a trace from a program that writes no marker) is
listed as `after <that span>` and counted as unowned.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import host_spans, trace_reduce  # noqa: E402
from harness.stats import interval_gaps  # noqa: E402


def device_window(chip: dict):
    """(operation intervals, first start, last end) of one chip's lines."""
    evs = chip.get(trace_reduce.OPS_LINE) or chip.get(trace_reduce.MODULES_LINE) or []
    every = [x for line in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)
             for x in chip.get(line, [])]
    return ([(s, e) for _, s, e in evs], min(s for _, s, _ in every),
            max(e for _, _, e in every))


def attribute(path: str, min_us: float = 50.0) -> dict:
    planes = trace_reduce.read_planes(path)
    if not planes:
        raise SystemExit("the trace holds no device plane")
    busy, lo, hi = device_window(planes[min(planes)])
    spans = host_spans.bounded(host_spans.read(path), lo, hi)
    last = spans[-1] if spans else None
    starts = [s[1] for s in spans]
    rows, by_span, owned, idle = [], {}, 0.0, 0.0
    for gs, ge in interval_gaps(busy, lo, hi):
        idle += ge - gs
        span = host_spans.open_at(spans, gs, starts)
        if span is not None:
            label = span[0]
            owned += ge - gs
        elif last is not None and gs >= last[2]:
            label = f"after {last[0]} (not recorded)"
        else:
            label = "no worker span"
        by_span[label] = by_span.get(label, 0.0) + (ge - gs)
        if (ge - gs) * 1e6 >= min_us:
            seq = span[3].get("seq") if span is not None else None
            rows.append({"start_ms": (gs - lo) * 1e3, "us": (ge - gs) * 1e6, "span": label,
                         **({"seq": int(seq)} if seq is not None else {})})
    return {
        "window_s": hi - lo, "idle_s": idle, "gaps": rows,
        "idle_by_span_s": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "idle_owned_pct": 100.0 * owned / idle if idle > 0 else None,
        "window_covered_pct": 100.0 * host_spans.covered(spans, lo, hi) / (hi - lo),
        "spans": len(spans),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--min-us", type=float, default=50.0)
    ap.add_argument("--top", type=int, default=40, help="gaps printed, longest first")
    ap.add_argument("--json", action="store_true", help="the whole result as one JSON object")
    args = ap.parse_args()
    path = args.trace if os.path.isfile(args.trace) else host_spans.find(args.trace)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.trace}")
    r = attribute(path, args.min_us)
    if args.json:
        print(json.dumps(r))
        return
    print(f"{path}: window {r['window_s']:.3f} s, idle {r['idle_s'] * 1e3:.3f} ms, "
          f"{len(r['gaps'])} gaps of {args.min_us:g} us or more, {r['spans']} worker spans")
    for g in sorted(r["gaps"], key=lambda g: -g["us"])[:args.top]:
        seq = f" seq {g['seq']}" if "seq" in g else ""
        print(f"  at {g['start_ms']:10.3f} ms  {g['us']:10.1f} us  {g['span']}{seq}")
    print("idle time by the span open when the gap began:")
    for k, v in r["idle_by_span_s"].items():
        print(f"  {v * 1e3:10.3f} ms  {k}")
    owned = "n/a" if r["idle_owned_pct"] is None else f"{r['idle_owned_pct']:.1f}%"
    print(f"idle time with an owner: {owned}; worker spans cover "
          f"{r['window_covered_pct']:.2f}% of the traced window")


if __name__ == "__main__":
    main()
