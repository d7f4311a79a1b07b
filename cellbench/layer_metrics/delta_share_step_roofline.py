"""Model step: the whole step's share of the chip's peak for the delta-rule /
attention hybrid that holds one chip's share of its routed experts. For each
traced launch the least time for what its steps must stream and compute
(roofline/delta_share_step.py: the mixers, routers, shared experts and the
head's slice once a step, THE HELD EXPERTS THE LAUNCH TOUCHED, every state
row-step's float32 state both ways, the useful K/V bytes; or the launch's
operations at the bf16 peak, the larger) over the device time of THAT
launch's execution of its step program (harness/host_spans.join_launches
pairs them). Useful bytes and operations only, so it cannot pass 100; it is
the bound a later claim in such a cell is read against. From a configuration
of another family, or a program or a trace without the launch spans, the
record's `state_rows` or the routed counts on the span that follows the
fetch, None."""
from harness import host_spans, manifest, trace_reduce


def read(ctx):
    trace = ctx.config.get("serving", {}).get("trace", {})
    path = host_spans.find(ctx.trace_dir)
    if "linear_attn_config" not in ctx.config or path is None \
            or "step_modules" not in trace:
        return None
    step = manifest.load_module("roofline", "delta_share_step")
    spans = host_spans.read(path)
    planes = trace_reduce.read_planes(path)
    if not spans or not planes:
        return None
    chip = planes[min(planes)]
    after = {int(st["seq"]): st for name, _, _, st in spans
             if name == "phase.distribute" and "seq" in st}
    least = seconds = 0.0
    for st, start, end in host_spans.join_launches(
            spans, chip.get(trace_reduce.MODULES_LINE, []), trace["step_modules"]):
        t = step.least_seconds(ctx.config, st, after.get(int(st["seq"]), {}), ctx.peaks)
        if t is not None:
            least, seconds = least + t, seconds + (end - start)
    return 100.0 * least / seconds if seconds > 0 else None
