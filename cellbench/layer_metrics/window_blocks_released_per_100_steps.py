"""Paged KV + prefix: window-group blocks rows gave back while they went on,
a hundred scheduler steps: the window's delta of
`dli_kv_window_blocks_released_total` over the steps the device ran, which
are a decode chunk's `dli_decode_chunk_steps_total{state="run"}` and one a
mixed launch (`dli_ragged_launches_total{phase="mixed"}`). A row of a full
fleet crosses a block edge every block-size steps and gives the block below
its window back: 32 rows at a window of one block of 128 read about 25; 0
would mean rows keep what they can no longer read. (Not over
`dli_launch_device_steps_total`: that counts the launches whose device time
the worker could tell alone, and would overstate by the share it could
not.) A pool of one group gives nothing back and reads 0. From a program
without the counters (the parent commit), or a window without a step:
None."""
from harness import scrape

RELEASED = "dli_kv_window_blocks_released_total"


def read(ctx):
    if not any(name == RELEASED for name, _ in ctx.after):
        return None
    steps = (scrape.delta(ctx.before, ctx.after, "dli_decode_chunk_steps_total", state="run")
             + scrape.delta(ctx.before, ctx.after, "dli_ragged_launches_total", phase="mixed"))
    if steps <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, RELEASED) / steps
