"""Continuous engine: scheduler steps per second of wall time over the
window. A mixed launch is one step and a pure-decode chunk is
--continuous-chunk steps; the program counts mixed launches
(`dli_ragged_launches_total{phase="mixed"}`) and observes
`dli_decode_step_seconds` once per fetch of either kind, so chunks are the
difference of the two counts."""
from harness import scrape


def steps(ctx):
    mixed = scrape.delta(ctx.before, ctx.after, "dli_ragged_launches_total", phase="mixed")
    fetches = scrape.delta(ctx.before, ctx.after, "dli_decode_step_seconds_count",
                           engine="continuous")
    return mixed + ctx.chunk_steps * max(0.0, fetches - mixed)


def read(ctx):
    n = steps(ctx)
    return n / ctx.window_s if n > 0 else None
