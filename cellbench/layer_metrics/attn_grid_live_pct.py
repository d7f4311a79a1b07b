"""Kernels: share of the KV positions the paged attention kernels' grids
walked in the window that some row had to read:
`dli_attn_kv_tokens_total{state="attended"}` over `{state="walked"}`, both
per layer and KV head. Attended is the least the launch's rows need by the
host's position model (a lower bound for a prefill chunk, which the kernel
reads once per query tile); walked is tiles or slots x the whole block
table x steps. Finding 1 of PR 23 as a number."""
from harness import scrape


def read(ctx):
    walked = scrape.delta(ctx.before, ctx.after, "dli_attn_kv_tokens_total", state="walked")
    if walked <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, "dli_attn_kv_tokens_total",
                                state="attended") / walked
