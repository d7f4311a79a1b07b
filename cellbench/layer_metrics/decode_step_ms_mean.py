"""Model step: the device's time a DECODE step, over the whole window and
with the profiler off: the delta of `dli_launch_device_seconds_total
{phase="chunk"}` over that of `dli_launch_device_steps_total{phase="chunk"}`.
The program's worker counts a launch's device time where two consecutive
fetches both had to wait for their result (utils/tracing.LaunchTimer), and
the steps the device RAN in those launches (a chunk cut short counts what it
ran, not the 16 dispatched). A window with no timed chunk, or a program
without the counters (an older commit): None."""
from harness import scrape


def step_ms(ctx, phase: str):
    """Timed device milliseconds a step of launches of kind `phase`, or None."""
    steps = scrape.delta(ctx.before, ctx.after, "dli_launch_device_steps_total", phase=phase)
    if steps <= 0:
        return None
    seconds = scrape.delta(ctx.before, ctx.after, "dli_launch_device_seconds_total", phase=phase)
    return 1e3 * seconds / steps


def read(ctx):
    return step_ms(ctx, "chunk")
