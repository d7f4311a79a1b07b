"""Continuous engine: share of the tokens the fleet processed in the window
that were prompt tokens: `dli_sched_step_tokens_total{kind="prefill"}` over
itself plus the output tokens that reached the clients (the program counts
decode tokens only in mixed launches, not in pure-decode chunks)."""
from harness import scrape


def read(ctx):
    prefill = scrape.delta(ctx.before, ctx.after, "dli_sched_step_tokens_total", kind="prefill")
    decode = ctx.end_to_end["out_tok_s"] * ctx.window_s
    return 100.0 * prefill / (prefill + decode) if prefill + decode > 0 else None
