"""From a profiler trace (.xplane.pb) to the numbers the per-layer metrics
read. Kept with the benchmark so that every PR computes them the same way.

A TPU trace has one plane per chip (`/device:TPU:<n>`) whose lines hold
device events with a start and a duration in nanoseconds: `XLA Modules`
(one event per execution of a jitted program, named `<module>(<id>)`) and
`XLA Ops` (one event per operation inside it, named by the HLO). The
program puts no `named_scope` or `TraceAnnotation` in yet, so these names
are all there is, and what the host did in a gap between device events
cannot be told: gaps are labelled `unattributed`, with the module that
ended the gap.

    busy_s     union of the `XLA Ops` intervals of a chip (an op nested in a
               `while` does not count twice), averaged over the chips that
               ran anything
    window_s   first device event's start to the last one's end (the
               profiler's own start-up and write-out are not in it)
    modules    {name: [durations_s]} per chip-0 execution, in time order
    ops        {name: total_s} summed over chips / chips; the name is the
               HLO instruction's (`%fusion.12`), without its operands, and
               control-flow containers (`while`, `conditional`, `call`), whose
               time is their children's, are left out

Which modules are scheduler steps, and which operations are the attention
kernels, is the program's business and differs between entry points (one
server, `--pp 4`, a router's replicas): a configuration's file names them
under `serving.trace`, and nothing here knows a name of the program's.
Read from a trace by hand for the two configurations of PR 23 (my chip
run): the fleet runs two programs, `jit_mixed_step_ragged` (one scheduler
step: decode rows + prefill chunks) and `jit_decode_slots_paged` (a chunk of
--continuous-chunk pure-decode steps in one `while`); the Pallas kernels
appear as custom calls named after their wrappers, `%ragged_paged_attend.N`
and `%paged_flash_attend.N`.
"""

from __future__ import annotations

import re

from harness.stats import interval_gaps, interval_union

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def op_name(event_name: str) -> str:
    """`%fusion.3 = bf16[..] fusion(...)` -> `%fusion.3`."""
    return event_name.split(" = ", 1)[0].strip()


def module_name(event_name: str) -> str:
    """`jit_mixed_step_ragged(1234567)` -> `jit_mixed_step_ragged`."""
    return _MODULE_ID.sub("", event_name).strip()


def read_planes(path: str) -> dict:
    """{chip: {line name: [(name, start_s, end_s)]}} of the device planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events
            ]
        out[int(m.group(1))] = lines
    return out


def reduce_planes(planes: dict) -> dict:
    chips = {c: ls for c, ls in planes.items() if ls.get(OPS_LINE) or ls.get(MODULES_LINE)}
    if not chips:
        raise SystemExit("the trace holds no device event: nothing ran on a chip")
    busy, spans, ops = [], [], {}
    for lines in chips.values():
        evs = lines.get(OPS_LINE) or lines[MODULES_LINE]
        busy.append(interval_union([(s, e) for _, s, e in evs]))
        every = [x for line in (OPS_LINE, MODULES_LINE) for x in lines.get(line, [])]
        spans.append((min(s for _, s, _ in every), max(e for _, _, e in every)))
        for name, s, e in lines.get(OPS_LINE, []):
            name = op_name(name)
            if not _CONTAINER.match(name):
                ops[name] = ops.get(name, 0.0) + (e - s)
    n = len(chips)
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    first = chips[min(chips)]
    modules = {}
    for name, s, e in sorted(first.get(MODULES_LINE, []), key=lambda x: x[1]):
        modules.setdefault(module_name(name), []).append(e - s)
    # idle gaps of the first chip, labelled by the module that ended each
    mods = sorted(first.get(MODULES_LINE, []), key=lambda x: x[1])
    evs = first.get(OPS_LINE) or first.get(MODULES_LINE)
    gaps = {}
    for gs, ge in interval_gaps([(s, e) for _, s, e in evs], lo, hi):
        nxt = next((module_name(nm) for nm, s, _ in mods if s >= ge - 1e-9), "end of trace")
        label = f"unattributed, before {nxt}"
        gaps[label] = gaps.get(label, 0.0) + (ge - gs)
    top_ops = sorted(((k, v / n) for k, v in ops.items()), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n, "window_s": hi - lo, "busy_s": sum(busy) / n,
        "modules": modules, "ops": {k: v / n for k, v in ops.items()},
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in top_gaps],
        },
    }


def reduce(path: str) -> dict:
    return reduce_planes(read_planes(path))


def summary(r: dict) -> str:
    mods = ", ".join(
        f"{k} x{len(v)} median {sorted(v)[len(v) // 2] * 1e3:.3f} ms total {sum(v):.3f} s"
        for k, v in sorted(r["modules"].items(), key=lambda kv: -sum(kv[1]))[:6]
    )
    return (f"{r['chips']} chip(s), window {r['window_s']:.3f} s, busy {r['busy_s']:.3f} s "
            f"(idle {100 * (1 - r['busy_s'] / r['window_s']):.1f}%); modules: {mods}")


def step_durations(ctx) -> list:
    """Device seconds of every scheduler step in the traced window. The
    configuration's `serving.trace.step_modules` maps a part of a module's
    name to the scheduler steps one execution of it makes (null: a chunk of
    `ctx.chunk_steps`); an execution of n steps counts as n steps of an n-th
    of its time each."""
    out = []
    step_modules = ctx.config["serving"]["trace"]["step_modules"]
    for name, durs in ctx.trace["modules"].items():
        for hint, steps in step_modules.items():
            if hint in name:
                n = steps or ctx.chunk_steps
                for d in durs:
                    out.extend([d / n] * n)
    return out


def kernel_seconds(ctx, kernels: str):
    """Summed device seconds of the operations whose name holds one of the
    configuration's `serving.trace.<kernels>`, or None where none ran."""
    names = ctx.config["serving"]["trace"][kernels]
    hits = [v for k, v in ctx.trace["ops"].items() if any(e in k for e in names)]
    return sum(hits) if hits else None
