"""Continuous engine: of the device seconds the window's DECODING rows lived
through, the share they spent in mixed steps, another request's prefill
beside them (`dli_decode_row_seconds_total{phase="mixed"}` over both phases:
a timed launch's device time x its decoding rows). `tpot_ms_p50` in an
open-loop cell is a mix of a short decode step and a long mixed step; this
is the mix. No decoding row in a timed launch of the window, or a program
without the counter: None."""
from harness import scrape

NAME = "dli_decode_row_seconds_total"


def read(ctx):
    both = scrape.delta(ctx.before, ctx.after, NAME)
    if both <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, NAME, phase="mixed") / both
