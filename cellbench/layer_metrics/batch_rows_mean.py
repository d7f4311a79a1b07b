"""Continuous engine: decode rows per scheduler step over the window: the
output tokens that reached the clients in the window (each is one row of one
step) over the steps the program's counters give (steps_per_s.batch.steps)."""
from harness import manifest


def read(ctx):
    n = manifest.load_module("layer_metrics", "steps_per_s.batch").steps(ctx)
    return ctx.end_to_end["out_tok_s"] * ctx.window_s / n if n > 0 else None
