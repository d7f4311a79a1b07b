"""Kernels: share of the KV positions visible to the sparse attention
layers' queries that the selection lets them read:
`dli_attn_kv_tokens_total{state="selected"}` over `{state="visible"}`, both
per layer and KV head by the host's position model (selected: every
position below the dense length, at most top-k blocks past it). 100 where
every row is below the dense length. From a program without the two
states, None."""
from harness import scrape


def read(ctx):
    visible = scrape.delta(ctx.before, ctx.after, "dli_attn_kv_tokens_total",
                           state="visible")
    if visible <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, "dli_attn_kv_tokens_total",
                                state="selected") / visible
