"""The traced launches of a run, for the readers that hold a launch's own
facts against its own device time: each `launch.*` span matched with the
execution of its step program (harness/host_spans.join_launches), with what
the worker wrote on the span that follows the launch's fetch (routed
experts: `moe_pairs`, `moe_experts_touched`), and the device seconds inside
those executions of the operations whose name holds one of `names`.

From a program without the spans, or a trace without them: None.
`layer_metrics/attn_kv_roofline.py` (PR 24) holds the same join inline; a
PR that may edit it moves it onto this."""

from __future__ import annotations

from harness import host_spans, trace_reduce


def read(ctx, kernels: str):
    """([(launch stats, after-fetch stats or {})], kernel seconds) or None.
    `kernels`: the key under the configuration's `serving.trace` that lists
    the operations' names; a configuration without it gives None."""
    path = host_spans.find(ctx.trace_dir)
    trace = ctx.config["serving"]["trace"]
    if path is None or kernels not in trace:
        return None
    spans = host_spans.read(path)
    planes = trace_reduce.read_planes(path)
    if not spans or not planes:
        return None
    chip = planes[min(planes)]
    matched = host_spans.join_launches(
        spans, chip.get(trace_reduce.MODULES_LINE, []), trace["step_modules"])
    if not matched:
        return None
    after = {int(st["seq"]): st for name, _, _, st in spans
             if name == "phase.distribute" and "seq" in st}
    inside = sorted((s, e) for _, s, e in matched)
    seconds = sum(
        e - s for name, s, e in chip.get(trace_reduce.OPS_LINE, [])
        if any(k in trace_reduce.op_name(name) for k in trace[kernels])
        and any(a <= s < b for a, b in inside))
    return [(st, after.get(int(st["seq"]), {})) for st, _, _ in matched], seconds
