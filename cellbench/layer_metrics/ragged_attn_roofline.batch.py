"""Kernels: the least time the chip needs to read the KV of the rows that
were decoding while the trace was taken (the generator's own record of each
live request's length, window-clipped; roofline/ragged_attention.py) over
the attention kernels' device time per step. Bandwidth-bound at decode
shapes. Only where prompts are unique and nearly every step is decode
(elsewhere the lengths the kernel walked are not knowable from outside)."""
from harness import manifest, trace_reduce


def read(ctx):
    steps = trace_reduce.step_durations(ctx)
    total = trace_reduce.kernel_seconds(ctx, "attention_kernels")
    live = [s["lengths"] for s in ctx.live if s["lengths"]]
    if not steps or total is None or not live:
        return None
    ra = manifest.load_module("roofline", "ragged_attention")
    least = sum(ra.bound(ctx.config, rows, ctx.peaks)[0] for rows in live) / len(live)
    return 100.0 * least / (total / len(steps))
