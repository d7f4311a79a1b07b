"""The device's time by the program's own scopes.

A v5e trace's `XLA Ops` events carry an instruction's name (`%fusion.12`)
and nothing of the `jax.named_scope` labels the step programs are written
under. Since ISSUE 38 the program writes the missing half itself: when a
profiler session ends, `<trace dir>/program_scopes.json` holds, for each
step program the session dispatched,

    {"vocabulary": [label, ...],
     "programs": {module name: {instruction name: {"scope": [labels,
                  outermost first], "mixed": n}}}}

(`mixed`: for a fusion, how many different innermost labels its fused
instructions hold; 0 or 1 is one scope's work). This file joins the two:
every `XLA Ops` event of the first chip goes to the `XLA Modules` event
that holds its start, because instruction names repeat between modules
(`trace_reduce`'s `ops` sums them by name alone), and then to its
instruction's scope in THAT module's map. Control-flow containers, whose
time is their children's, are left out as `trace_reduce` leaves them out.

From a trace with no `program_scopes.json` beside it (an older commit's)
`attribute` returns None, and so does every reader built on it. A map that
is there and does not fit (under 99% of a mapped module's device seconds
carry a name the map knows) is the program's fault and raises. A step
module the map does not hold at all is one the session never dispatched (a
launch in flight when it began, of a kind not launched again): its seconds
count as unscoped and it is named in `unmapped_modules`.
"""

from __future__ import annotations

import bisect
import json
import os

from harness import host_spans, trace_reduce

FILE = "program_scopes.json"
FIT = 0.99  # of a mapped module's device seconds


def load(trace_dir):
    """The map beside a profile, or None where the program wrote none."""
    path = os.path.join(trace_dir or "", FILE)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def attribute(chip: dict, programs: dict, step_modules) -> dict:
    """Device seconds of one chip's step-program executions, by scope.

    chip: {line name: [(event name, start_s, end_s)]} (one entry of
    `trace_reduce.read_planes`); programs: the map's `programs`;
    step_modules: the parts of a module's name that mark a step program
    (the configuration's `serving.trace.step_modules`). Returns

        {"modules": {module: {"executions": [seconds, ...],
                              "seconds": device seconds of its operations,
                              "by_scope": {"attn/mla_absorb": s, ..., "": s},
                              "unlabelled": {instruction: s},
                              "mixed": {instruction: s},
                              "unknown": {instruction: s}}},
         "unmapped_modules": [module, ...]}

    `by_scope`'s key joins an instruction's labels, outermost first ("" for
    one under no label: the `unlabelled` instructions); `mixed` holds the
    fusions of more than one scope's work (their time is in `by_scope`
    under the fusion's own label all the same); `unknown` the names the
    module's map does not hold."""
    mods = sorted(
        (s, e, trace_reduce.module_name(n))
        for n, s, e in chip.get(trace_reduce.MODULES_LINE, [])
        if any(h in n for h in step_modules))
    starts = [m[0] for m in mods]
    out = {}
    for s, e, name in mods:
        out.setdefault(name, {"executions": [], "seconds": 0.0, "by_scope": {},
                              "unlabelled": {}, "mixed": {}, "unknown": {}})
        out[name]["executions"].append(e - s)
    for ev, s, e in chip.get(trace_reduce.OPS_LINE, []):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1]:
            continue  # not inside a step program's execution
        inst = trace_reduce.op_name(ev)
        if trace_reduce._CONTAINER.match(inst):
            continue
        mod = out[mods[i][2]]
        mod["seconds"] += e - s
        held = programs.get(mods[i][2], {}).get(inst)
        if held is None:
            mod["unknown"][inst] = mod["unknown"].get(inst, 0.0) + (e - s)
            continue
        key = "/".join(held["scope"])
        mod["by_scope"][key] = mod["by_scope"].get(key, 0.0) + (e - s)
        if not held["scope"]:
            mod["unlabelled"][inst] = mod["unlabelled"].get(inst, 0.0) + (e - s)
        if held["mixed"] > 1:
            mod["mixed"][inst] = mod["mixed"].get(inst, 0.0) + (e - s)
    unmapped = sorted(m for m in out if m not in programs)
    for name, mod in out.items():
        known = mod["seconds"] - sum(mod["unknown"].values())
        if name in programs and known < FIT * mod["seconds"]:
            worst = sorted(mod["unknown"].items(), key=lambda kv: -kv[1])[:5]
            raise SystemExit(
                f"{FILE} does not fit its trace: in {name} the map names "
                f"{100 * known / mod['seconds']:.2f}% of the device seconds "
                f"(under {100 * FIT:g}%); largest unknown instructions {worst}")
    return {"modules": out, "unmapped_modules": unmapped}


def read(ctx):
    """`attribute` of a run's trace (kept on `ctx`: six metrics read it),
    or None where there is no map, no trace or no step program in it."""
    if "_program_scopes" not in ctx.__dict__:
        ctx._program_scopes = _read(ctx)
    return ctx._program_scopes


def _read(ctx):
    held = load(ctx.trace_dir)
    path = host_spans.find(ctx.trace_dir)
    if held is None or path is None:
        return None
    planes = trace_reduce.read_planes(path)
    if not planes:
        return None
    got = attribute(planes[min(planes)], held["programs"],
                    ctx.config["serving"]["trace"]["step_modules"])
    return got if got["modules"] else None


def scope_seconds(got: dict, labels) -> float:
    """Seconds under any of `labels` (an instruction counts by its
    outermost label: `mla_absorb` is `attn`'s)."""
    return sum(s for mod in got["modules"].values()
               for key, s in mod["by_scope"].items() if key.split("/")[0] in labels)


def ms_per_step(ctx, labels):
    """Device milliseconds under `labels` per scheduler step of the trace
    (`trace_reduce.step_durations`' count, the denominator of
    `moe_ms_per_step` and `attn_kernel_ms_per_step`), or None: no map, no
    step, or no instruction of the traced programs under these labels."""
    got = read(ctx)
    steps = trace_reduce.step_durations(ctx)
    if got is None or not steps:
        return None
    if not any(key.split("/")[0] in labels for mod in got["modules"].values()
               for key in mod["by_scope"]):
        return None
    return 1e3 * scope_seconds(got, labels) / len(steps)
