"""Model step: device time of one scheduler step, median over the traced
window: an execution of the mixed-step module is one step, an execution of
the 16-step decode module sixteen steps of a sixteenth each (device trace,
`XLA Modules`)."""
from harness import stats, trace_reduce


def read(ctx):
    steps = trace_reduce.step_durations(ctx)
    return 1e3 * stats.percentile(steps, 50, enforce=False) if steps else None
