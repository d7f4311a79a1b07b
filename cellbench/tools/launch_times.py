#!/usr/bin/env python3
"""The worker's reading of the device against the device's own, over a trace.

The program's worker thread times the launches it feeds the device with no
profiler (utils/tracing.LaunchTimer): where two consecutive fetches both had
to wait, the time between their returns is the later launch's device time.
It writes that on the `phase.distribute` span after each fetch (`seq`,
`timed` 0 | 1, `device_us`), says on `launch.*` whether the launch met an
empty queue (`queue_empty`) and on `fetch.*` whether the result was there
when it arrived (`ready`). This tool holds each against the trace:

    python3 cellbench/tools/launch_times.py <file.xplane.pb | trace dir> [--config <configuration file>] [--json]

Launch by launch (those whose fetch returned inside the trace): the worker's
`device_us` beside the duration of the launch's step module on the first
chip (harness/host_spans.join_launches, by `seq`) and their difference; then
per launch kind the two sums over the timed launches, the median and the
worst difference, and how many launches were untimed and why (`queue_empty`,
`ready_early`; `absent`: the program wrote no `timed`, an older commit).
Last, the seconds of the traced window in which no launch was unfetched,
by the worker's phase (what `dli_device_empty_seconds_total{phase}` counts
over the whole run), against the chip's idle time over the same seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from gaps import device_window  # noqa: E402
from harness import host_spans, trace_reduce  # noqa: E402
from harness.stats import interval_gaps  # noqa: E402

STEP_MODULES = {"mixed_step_ragged": 1, "decode_slots_paged": None}


def launches(spans: list, modules: list, step_modules: dict) -> list:
    """One row per launch whose fetch returned inside the trace, by seq."""
    joined = {int(st["seq"]): (st, s, e)
              for st, s, e in host_spans.join_launches(spans, modules, step_modules)}
    dispatched = {int(st["seq"]): st for name, _, _, st in spans
                  if name.startswith("launch.") and "seq" in st}
    closed = {int(st["seq"]): st for name, _, _, st in spans
              if name == "phase.distribute" and "seq" in st}
    rows = []
    for name, _, _, st in spans:
        if not name.startswith("fetch.") or "seq" not in st:
            continue
        seq = int(st["seq"])
        launch, after = dispatched.get(seq, {}), closed.get(seq, {})
        row = {"seq": seq, "kind": name[len("fetch."):]}
        if "ready" in st:
            row["ready"] = int(st["ready"])
        if "queue_empty" in launch:
            row["queue_empty"] = int(launch["queue_empty"])
        if "timed" not in after:
            row["state"] = "absent"
        elif int(after["timed"]):
            row["state"], row["device_us"] = "timed", int(after["device_us"])
        else:  # a launch dispatched before the trace began left no span: its own
            # or the previous fetch was late unless it says it met an empty queue
            row["state"] = "queue_empty" if row.get("queue_empty") else "ready_early"
        if seq in joined:
            row["module_us"] = (joined[seq][2] - joined[seq][1]) * 1e6
        rows.append(row)
    return sorted(rows, key=lambda r: r["seq"])


def sums(rows: list) -> dict:
    """Per launch kind: outcomes, and over the timed launches that have their
    module the worker's sum against the modules'."""
    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        mine = [r for r in rows if r["kind"] == kind]
        both = [r for r in mine if r["state"] == "timed" and "module_us" in r]
        diffs = [r["device_us"] - r["module_us"] for r in both]
        worker, module = sum(r["device_us"] for r in both), sum(r["module_us"] for r in both)
        out[kind] = {
            "launches": len(mine),
            **{s: sum(r["state"] == s for r in mine)
               for s in ("timed", "queue_empty", "ready_early", "absent")},
            "timed_with_module": len(both),
            "worker_us": worker, "module_us": module,
            "worker_over_module_pct": 100.0 * (worker / module - 1.0) if module else None,
            "diff_us_median": statistics.median(diffs) if diffs else None,
            "diff_us_worst": max(diffs, key=abs) if diffs else None,
        }
    return out


def empty_flags(spans: list):
    """For each span (markers taken out, head and tail put back): was no
    launch unfetched while it was open? A `launch.*` span says so for itself
    and for everything since the fetch before it; nothing is empty between a
    launch and the fetch that drains the queue, nor between two fetches. None
    where the program wrote no `queue_empty` (an older commit)."""
    flags, state, seen, fetched = [None] * len(spans), None, -1, -1

    def back_fill(i, value):
        while i > 0 and flags[i - 1] is None:
            i -= 1
            flags[i] = value

    for i, (name, _, _, st) in enumerate(spans):
        if name.startswith("launch."):
            if "queue_empty" not in st:
                return None
            flags[i] = bool(int(st["queue_empty"]))
            back_fill(i, flags[i])
            state, seen = False, max(seen, int(st.get("seq", -1)))
        elif name.startswith("fetch.") or name == "phase.fetch_wait":  # or the head
            flags[i] = False
            back_fill(i, False)
            state, fetched = None, max(fetched, int(st.get("seq", -1)))
        else:
            flags[i] = state
    # after the last fetch: empty unless a launch seen since is unfetched
    back_fill(len(spans), seen <= fetched)
    return flags


def empty_queue(spans: list, busy: list, lo: float, hi: float):
    """Seconds of [lo, hi] with no launch unfetched, by the worker's phase,
    and the chip's idle seconds inside and outside them."""
    flags = empty_flags(spans)
    if flags is None:
        return None
    gaps = interval_gaps(busy, lo, hi)
    by_phase, empty = {}, []
    for (name, s, e, _), flag in zip(spans, flags):
        s, e = max(s, lo), min(e, hi)
        if flag and e > s:
            phase = {"launch": "dispatch", "fetch": "fetch_wait"}.get(
                name.split(".")[0], name.split(".", 1)[-1])
            by_phase[phase] = by_phase.get(phase, 0.0) + (e - s)
            empty.append((s, e))
    idle = sum(e - s for s, e in gaps)
    inside, i = 0.0, 0  # both lists are in time order and neither overlaps itself
    for s, e in gaps:
        while i < len(empty) and empty[i][1] <= s:
            i += 1
        j = i
        while j < len(empty) and empty[j][0] < e:
            inside += min(e, empty[j][1]) - max(s, empty[j][0])
            j += 1
    total = sum(by_phase.values())
    return {
        "window_s": hi - lo, "idle_s": idle, "idle_pct": 100.0 * idle / (hi - lo),
        "empty_s_by_phase": dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
        "empty_s": total, "empty_pct": 100.0 * total / (hi - lo),
        "empty_wait_pct": 100.0 * by_phase.get("wait_work", 0.0) / (hi - lo),
        "empty_host_pct": 100.0 * (total - by_phase.get("wait_work", 0.0)) / (hi - lo),
        "idle_inside_empty_s": inside, "idle_elsewhere_s": idle - inside,
    }


def report(path: str, step_modules: dict = STEP_MODULES) -> dict:
    planes = trace_reduce.read_planes(path)
    if not planes:
        raise SystemExit("the trace holds no device plane")
    chip = planes[min(planes)]
    busy, lo, hi = device_window(chip)
    raw = host_spans.read(path)
    rows = launches(raw, chip.get(trace_reduce.MODULES_LINE, []), step_modules)
    return {"trace": path, "launches": rows, "sums": sums(rows),
            "empty_queue": empty_queue(host_spans.bounded(raw, lo, hi), busy, lo, hi)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--config", help="the configuration's file, for serving.trace.step_modules")
    ap.add_argument("--json", action="store_true", help="the whole result as one JSON object")
    args = ap.parse_args()
    path = args.trace if os.path.isfile(args.trace) else host_spans.find(args.trace)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.trace}")
    step_modules = STEP_MODULES
    if args.config:
        with open(args.config) as f:
            step_modules = json.load(f)["serving"]["trace"]["step_modules"]
    r = report(path, step_modules)
    if args.json:
        print(json.dumps(r))
        return
    print(f"{path}: {len(r['launches'])} launches fetched inside the trace")
    print("     seq kind  queue_empty ready state         worker_us   module_us     diff_us")
    for row in r["launches"]:
        worker, module = row.get("device_us"), row.get("module_us")
        diff = worker - module if worker is not None and module is not None else None
        cells = [f"{v:11.1f}" if v is not None else " " * 11 for v in (worker, module, diff)]
        print(f"  {row['seq']:6d} {row['kind']:5s} {row.get('queue_empty', '-')!s:>11} "
              f"{row.get('ready', '-')!s:>5} {row['state']:11s} " + " ".join(cells))
    for kind, s in r["sums"].items():
        print(f"{kind}: {s['launches']} launches: timed {s['timed']}, queue_empty "
              f"{s['queue_empty']}, ready_early {s['ready_early']}, absent {s['absent']}")
        if s["timed_with_module"]:
            print(f"  over {s['timed_with_module']} timed launches with their module: worker "
                  f"{s['worker_us'] / 1e3:.3f} ms, modules {s['module_us'] / 1e3:.3f} ms "
                  f"({s['worker_over_module_pct']:+.2f}%); difference a launch: median "
                  f"{s['diff_us_median']:+.1f} us, worst {s['diff_us_worst']:+.1f} us")
    e = r["empty_queue"]
    if e is None:
        print("the program wrote no queue_empty on its launch spans: no empty-queue reading")
        return
    print(f"window {e['window_s']:.3f} s: the chip idle {e['idle_s'] * 1e3:.3f} ms "
          f"({e['idle_pct']:.2f}%); no launch unfetched {e['empty_s'] * 1e3:.3f} ms "
          f"({e['empty_pct']:.2f}% = wait_work {e['empty_wait_pct']:.2f} + the host's phases "
          f"{e['empty_host_pct']:.2f}); idle inside those seconds "
          f"{e['idle_inside_empty_s'] * 1e3:.3f} ms, elsewhere {e['idle_elsewhere_s'] * 1e3:.3f} ms")
    for k, v in e["empty_s_by_phase"].items():
        print(f"  {v * 1e3:10.3f} ms  {k}")


if __name__ == "__main__":
    main()
