"""Device: share of the window in which the device's queue stood empty (no
launch dispatched and unfetched) while the worker waited for a request
(`dli_device_empty_seconds_total{phase="wait_work"}` over the window): idle
time that is the traffic's. With `device_empty_host_pct` a lower bound on
`device_idle_pct`, over the whole window and with the profiler off. A
program without the counter (an older commit): None."""
from harness import scrape

NAME = "dli_device_empty_seconds_total"


def empty_pct(ctx, waiting: bool):
    """The window's empty-queue seconds in `wait_work` (waiting) or in every
    other phase, as a share of the window; None without the counter."""
    if not any(name == NAME for name, _ in ctx.after):
        return None
    wait = scrape.delta(ctx.before, ctx.after, NAME, phase="wait_work")
    seconds = wait if waiting else scrape.delta(ctx.before, ctx.after, NAME) - wait
    return 100.0 * seconds / ctx.window_s


def read(ctx):
    return empty_pct(ctx, waiting=True)
