"""Kernels: the least time the chip needs for the delta rule's state updates
of the traced launches (each row-step's float32 state read and written plus
its tokens' q, k, v, g, beta in and o out at the peak HBM bandwidth, or the
recurrence's own operations at the bf16 peak, the larger:
roofline/delta_rule.py) over the device time under the `delta_scan` scope
(nested in `delta_mix`: the chunk algebra and the carried state's program,
in a mixed step and in a decode step alike) in THOSE launches' executions of
their step programs. Both sides are of the launches the trace matches with a
span (harness/host_spans.join_launches): an execution with no span, one
dispatched before the profiler started or fetched after it stopped, gives
neither its work nor its time, so the share does not move with how many of
them a trace happens to hold. From a configuration without
`linear_attn_config`, or a program or a trace without the scope or the
record's `state_rows`, None."""
from harness import host_spans, manifest, program_scopes, trace_reduce

LABEL = "delta_scan"


def read(ctx):
    trace = ctx.config.get("serving", {}).get("trace", {})
    if "linear_attn_config" not in ctx.config or "step_modules" not in trace:
        return None
    held = program_scopes.load(ctx.trace_dir)
    path = host_spans.find(ctx.trace_dir)
    if held is None or path is None:
        return None
    spans = host_spans.read(path)
    planes = trace_reduce.read_planes(path)
    if not spans or not planes:
        return None
    chip = planes[min(planes)]
    modules = chip.get(trace_reduce.MODULES_LINE, [])
    matched = [m for m in host_spans.join_launches(spans, modules, trace["step_modules"])
               if "state_rows" in m[0]]
    inside = {(s, e) for _, s, e in matched}
    only = dict(chip)
    only[trace_reduce.MODULES_LINE] = [m for m in modules if (m[1], m[2]) in inside]
    got = program_scopes.attribute(only, held["programs"], trace["step_modules"])
    seconds = sum(s for mod in got["modules"].values()
                  for key, s in mod["by_scope"].items() if LABEL in key.split("/"))
    if seconds <= 0:
        return None
    rule = manifest.load_module("roofline", "delta_rule")
    return 100.0 * rule.bound(ctx.config, [st for st, _, _ in matched], ctx.peaks)[0] / seconds
