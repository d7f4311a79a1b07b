"""Routed experts, the grouped kernels' part only: device time of the expert
layers' grouped matrix product kernels (`serving.trace.expert_kernels`:
three a layer, gate, up, down) per scheduler step: their summed durations in
the trace over the steps traced. NOT the whole routed layer, which ISSUE 28
defined it as: route, sort, gather, combine and the shared expert run as
fusions under the step's `moe_*` scopes, and a v5e trace's events hold an
instruction's own text and no scope, so no reader can tell those fusions
from any other (PERF.md section 7, question 16: until the program or the
harness maps instructions to scopes, this is a lower bound on the layer).
From a configuration without `expert_kernels` None."""
from harness import trace_reduce


def read(ctx):
    if "expert_kernels" not in ctx.config["serving"]["trace"]:
        return None
    steps = trace_reduce.step_durations(ctx)
    total = trace_reduce.kernel_seconds(ctx, "expert_kernels")
    if not steps or total is None:
        return None
    return 1e3 * total / len(steps)
