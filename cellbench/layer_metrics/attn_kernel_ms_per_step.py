"""Kernels: device time of the paged attention kernels per scheduler step:
summed durations of their events in the trace (told apart by the names in
the configuration's `serving.trace.attention_kernels`: here the ragged
kernel of mixed steps and the paged decode kernel of pure-decode chunks)
over the steps traced."""
from harness import trace_reduce


def read(ctx):
    steps = trace_reduce.step_durations(ctx)
    total = trace_reduce.kernel_seconds(ctx, "attention_kernels")
    if not steps or total is None:
        return None
    return 1e3 * total / len(steps)
