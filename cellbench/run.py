#!/usr/bin/env python3
"""cellbench — one run of one benchmark cell, measured from the client's side.

    python3 cellbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of BENCHMARK.json's `workloads`: a configuration
(cellbench/configs/) under a traffic mix (cellbench/traffic/) at the cell's
own rate (cellbench/cells/). This process never initialises a JAX backend:
it starts the program's server as a child that holds the chip, drives it
over HTTP from the seed, checks the outputs against the plain reference
(harness/check.py), and prints the contract's JSON object as its last line.
Phases: set-up (server start, weights, warm-up) -> ramp -> measured window
-> drain -> output check -> server stopped -> reference child -> result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from harness import check, launcher, scrape, stats, tokenizer  # noqa: E402
from harness.load import Fleet, run_closed, run_open  # noqa: E402
from harness.manifest import ROOT, Cell, load_json, load_module  # noqa: E402
from harness.traffic_lib import Words  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


def cache_dir() -> str:
    """The program's own rule (utils/compile_cache.py): the operator's
    directory where set, else a fixed one inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".xla_cache")


def cache_entries() -> int:
    d = cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


class Context:
    """What a per-layer metric's reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def flag_value(flags: list, name: str, default=None):
    return flags[flags.index(name) + 1] if name in flags else default


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument(
        "--platform", default="tpu", choices=["tpu", "cpu"],
        help="JAX_PLATFORMS of the children; cpu rehearses every phase "
             "(interpreted kernels) and then exits non-zero",
    )
    ap.add_argument(
        "--server-flag", action="append", default=[],
        help="extra flag for the server (the control: --server-flag=--quant "
             "--server-flag=int8); a run with one never prints a result",
    )
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest (the tests' tiny one)")
    ap.add_argument("--check-only", action="store_true",
                    help="skip ramp and window: set-up and the output check alone (what "
                         "tools/control.py and the control's test read)")
    args = ap.parse_args()

    manifest = load_json(args.manifest)
    cell = Cell(manifest, args.workload)
    config, traffic, load = cell.config, cell.traffic, cell.load
    flags = config["serving"]["flags"]
    block = int(flag_value(flags, "--kv-block-size", 16))
    pool_blocks = int(flag_value(flags, "--kv-pool-blocks", 0))
    peaks_table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    window_s = float(args.seconds)
    ramp_s = float(traffic.get("ramp_s", 8.0))
    say(f"cell {cell.name}: configuration {config['name']} under {cell.entry['traffic']} "
        f"{json.dumps(load)}, {cell.chips} chip(s), seed {args.seed}, window {window_s:g} s, "
        f"ramp {ramp_s:g} s, trace {args.trace}")

    words = Words(config["vocab_size"], config.get("stop_token_ids", ()))
    tok_dir = tokenizer.ensure(launcher.state_dir(), config["vocab_size"])
    gen = load_module("generators", traffic["generator"])
    tag = f"{cell.name}.seed{args.seed}.trace{args.trace}"
    n_cache0 = cache_entries()
    server = launcher.Server(
        cell.config_path, config, args.seed, args.platform, tok_dir, tag,
        extra_flags=args.server_flag,
    )
    try:
        server.start()
        device = server.device()
        mem0 = server.memory()
        say(f"server ready in {server.ready_s:.1f} s; device {json.dumps(device)}; "
            f"compile cache {cache_dir()} {n_cache0} -> {cache_entries()} entries; "
            f"pool {pool_blocks} blocks x {block} tokens; bytes_in_use after warm-up "
            f"{[m.get('bytes_in_use') for m in mem0]}")
        if device["count"] < cell.chips:
            raise SystemExit(f"the cell needs {cell.chips} chips, the server holds {device['count']}")

        def fleet_factory():
            return Fleet("127.0.0.1", server.port)

        ctx = None
        if not args.check_only:
            ctx = measure(args, cell, server, gen, words, fleet_factory, window_s, pool_blocks)
        # ---- the output check: after the drain, outside every timing
        seqs = check.collect(fleet_factory, traffic.get("check"), words, args.seed,
                             int(flag_value(flags, "--prefix-cache", 0)) > 0, say)
        say("server log, events that matter: " + json.dumps(server.log_events()))
        mem1 = server.memory()
        peak = max([m.get("peak_bytes_in_use", m.get("bytes_in_use", 0)) or 0
                    for m in mem1] or [0])
        say(f"device memory after the run: {json.dumps(mem1)}")
        trace_file = None
        if ctx is not None and ctx.trace_dir:
            trace_file = find_trace(ctx.trace_dir)
    finally:
        server.stop()
    # ---- the server has gone; the chip is free for the reference
    good = [s for s in seqs if "ids" in s]
    out = check.run_reference(good, cell.config_path, args.seed, args.platform,
                              cache_dir(), tag, say) if good else {}
    if out:
        say(f"reference child: {json.dumps(out.get('seconds'))} s on "
            f"{json.dumps(out.get('device'))}")
    correct = check.judge(seqs, out, config["check"], say)
    if args.check_only:
        say(f"check-only: correct={correct}")
        return 0 if correct else 1

    metrics = {}
    if args.trace:
        from harness import trace_reduce

        if trace_file is None:
            raise SystemExit("no .xplane.pb came out of the traced window")
        ctx.trace = trace_reduce.reduce(trace_file)
        say(f"trace {trace_file}: {trace_reduce.summary(ctx.trace)}")
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        ctx.peaks = peaks_table.get(device["kind"])
        if ctx.peaks is None:
            raise SystemExit(f"device kind {device['kind']!r} is not in cellbench/peaks.json")
        for m in cell.per_layer:
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(ctx.end_to_end[m["name"]]), "unit": m["unit"]}
    device["memory_peak_bytes"] = int(peak)
    result = {
        "correct": bool(correct), "attempted": ctx.attempted, "failed": ctx.failed,
        "metrics": metrics, "device": device,
    }
    if args.trace:
        result["breakdown"] = ctx.trace["breakdown"]
    if args.server_flag:
        say(f"a run with --server-flag prints no result (would have been: {json.dumps(result)})")
        return 1
    if device["platform"] != "tpu":
        say(f"every phase ran, but the device is not a TPU ({device['platform']}): no result")
        return 3
    print(json.dumps(result), flush=True)
    return 0


def find_trace(trace_dir: str):
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    return None


def measure(args, cell, server, gen, words, fleet_factory, window_s, pool_blocks) -> Context:
    """Ramp, measured window, drain: the requests, scrapes and trace of one run."""
    traffic, load = cell.traffic, cell.load
    ramp_s = float(traffic.get("ramp_s", 8.0))
    tail_s = float(traffic.get("tail_s", 4.0))  # load goes on this long after the window
    drain_s = float(traffic.get("drain_s", 20.0))  # window requests are waited for this long
    fleet = fleet_factory()
    closed = load["loop"] == "closed"
    if closed:
        plan = gen.ClosedPlan(traffic, load, args.seed, words)
    else:
        sessions = gen.plan_open(traffic, load, args.seed, [ramp_s, window_s, tail_s], words)
    setup_s = time.monotonic() - T_PROCESS_START
    t0 = time.monotonic()
    w0, w1 = t0 + ramp_s, t0 + ramp_s + window_s
    fleet.offer_until = w1 + tail_s
    marks, scrapes, live = {}, [], []

    def mark(name):
        marks[name] = {
            "t": time.monotonic(), "metrics": scrape.parse(server.get("/metrics", raw=True).decode()),
            "cache": cache_entries(), "inflight": fleet.inflight(),
        }

    def snapshot_live():
        rows = []
        for r in list(fleet.results):
            if r.status == "inflight" and r.first is not None:
                rows.append(r.request.n_prompt + (r.events[-1][1] if r.events else 1))
        live.append({"t": time.monotonic(), "lengths": rows})

    trace_dir = None
    trace_info = {}

    def tracer():
        nonlocal trace_dir
        name = f"{cell.name}.seed{args.seed}"
        shutil.rmtree(os.path.join(server.trace_base, name), ignore_errors=True)
        trace_s = float(traffic.get("trace_s", 4.0))
        time.sleep(max(0.0, w0 + window_s / 3.0 - time.monotonic()))
        snapshot_live()
        res = server.post("/profiler/start", {"trace_dir": name})
        t_start = time.monotonic()
        time.sleep(trace_s / 2)
        snapshot_live()
        time.sleep(max(0.0, t_start + trace_s - time.monotonic()))
        snapshot_live()
        stop = server.post("/profiler/stop", {}, timeout=300)
        trace_info.update(start=res, stop=stop, t_start=t_start, t_stop=time.monotonic())
        trace_dir = stop.get("trace_dir") or res.get("trace_dir")

    def sampler():  # 1 Hz scrapes through the window (traced runs only)
        while time.monotonic() < w1:
            if time.monotonic() >= w0:
                scrapes.append(scrape.parse(server.get("/metrics", raw=True).decode()))
            time.sleep(1.0)

    helpers = []
    if args.trace:
        helpers = [threading.Thread(target=f, daemon=True) for f in (tracer, sampler)]
        for h in helpers:
            h.start()
    if closed:
        run_closed(fleet, plan, t0)
    else:
        run_open(fleet, sessions, t0)
    time.sleep(max(0.0, w0 - time.monotonic()))
    mark("w0")
    time.sleep(max(0.0, w1 - time.monotonic()))
    mark("w1")
    # ---- drain: requests due in the window are waited for, up to the limit
    def window_due():
        return [r for r in list(fleet.results) if w0 <= r.due < w1]

    deadline = w1 + drain_s
    if not closed:
        while time.monotonic() < deadline and (
            time.monotonic() < fleet.offer_until
            or any(r.status == "inflight" for r in window_due())
        ):
            time.sleep(0.05)
    for h in helpers:
        h.join(timeout=320)
    t_cancel = time.monotonic()
    fleet.cancel_open()
    stuck = fleet.join(30)
    if stuck:
        say(f"{len(stuck)} load threads did not end within 30 s of the cancel: "
            f"their requests count as failed")

    results = list(fleet.results)
    if closed:
        timed = [r for r in results if r.done is not None and w0 <= r.done < w1]
    else:
        timed = window_due()
    ok = [r for r in timed if r.ok and r.done is not None and r.done <= t_cancel]
    attempted, failed = len(timed), len(timed) - len(ok)
    by_status = {}
    for r in timed:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    ttft = [(r.first - r.due) * 1e3 for r in ok]
    tpot = [(r.done - r.first) * 1e3 / (r.tokens - 1) for r in ok if r.tokens > 1]
    late = [(r.sent - r.due) * 1e3 for r in timed if r.sent]
    tokens_in = sum(stats.tokens_in_window(r.events, w0, w1) for r in results)
    e2e = {"setup_s": setup_s, "out_tok_s": tokens_in / window_s}
    pct = lambda xs, p, enforce=True: stats.percentile(xs, p, enforce=enforce)  # noqa: E731
    for m in cell.end_to_end:  # ttft_ms_p50, tpot_ms_p90, ...: the name says which
        hit = re.fullmatch(r"(ttft|tpot)_ms_p(\d+)", m["name"])
        if hit:
            e2e[m["name"]] = pct(ttft if hit.group(1) == "ttft" else tpot, float(hit.group(2)))
    say(f"window: {attempted} requests timed, {failed} failed, by status {by_status}; "
        f"in flight at window start {marks['w0']['inflight']} and end {marks['w1']['inflight']}; "
        f"compiles in the window (new cache entries) "
        f"{marks['w1']['cache'] - marks['w0']['cache']}")
    if ttft:
        say(f"ttft ms over {len(ttft)}: p50 {pct(ttft, 50, False):.2f} p95 {pct(ttft, 95, False):.2f} "
            f"max {max(ttft):.2f}; tpot ms over {len(tpot)}: p50 {pct(tpot, 50, False):.3f} "
            f"p95 {pct(tpot, 95, False):.3f}; generator lateness ms over {len(late)}: "
            f"p50 {pct(late, 50, False):.3f} p95 {pct(late, 95, False):.3f} max {max(late):.3f}")
    whole = sum(n - m for r in results
                for (_, m), (t, n) in zip([(0.0, 0)] + r.events, r.events) if w0 <= t < w1)
    say(f"output tokens delivered in the window: {tokens_in:.1f} = {tokens_in / window_s:.2f} tokens/s "
        f"(every delivery counted whole at its arrival: {whole} = {whole / window_s:.2f}); "
        f"prompt tokens of timed requests {sum(r.prompt_tokens for r in ok)}, "
        f"of which prefix-cached {sum(r.cached_tokens for r in ok)}; setup_s {setup_s:.2f}")
    if trace_info:
        say(f"profiler: start {trace_info['start']} stop {trace_info['stop']} "
            f"({trace_info['t_stop'] - trace_info['t_start']:.2f} s on the host clock)")
    return Context(
        cell=cell, config=cell.config, window_s=window_s, results=results, timed=timed,
        ok=ok, attempted=attempted, failed=failed, end_to_end=e2e, late_ms=late,
        before=marks["w0"]["metrics"], after=marks["w1"]["metrics"], scrapes=scrapes,
        live=live, pool_blocks=pool_blocks, trace_dir=trace_dir,
        trace=None, peaks=None, closed=closed,
        chunk_steps=int(flag_value(cell.config["serving"]["flags"], "--continuous-chunk", 16)),
    )


if __name__ == "__main__":
    sys.exit(main())
