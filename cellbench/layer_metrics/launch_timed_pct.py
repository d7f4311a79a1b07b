"""Continuous engine: the share of the window's fetched launches whose device
time the worker could tell (`dli_launch_timing_total{state="timed"}` over
every state): what `decode_step_ms_mean`, `mixed_step_ms_mean` and
`decode_time_in_mixed_pct` rest on. The rest met an empty queue
(`queue_empty`: the chip had nothing to do before them) or were fetched late
(`ready_early`: the host, not the chip, set the pace there). No launch
fetched in the window, or a program without the counter: None."""
from harness import scrape

NAME = "dli_launch_timing_total"


def read(ctx):
    every = scrape.delta(ctx.before, ctx.after, NAME)
    if every <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, NAME, state="timed") / every
