"""Block diffusion: share of the window's row-forwards that were commits
(`dli_diffusion_row_forwards_total{kind="commit"}` over both kinds): the
forwards that reveal nothing and only write the clean block's K/V, which a
commit fused into the next block's first forward would remove. From a
program without the counter None."""
from harness import scrape


def read(ctx):
    total = scrape.delta(ctx.before, ctx.after, "dli_diffusion_row_forwards_total")
    if total <= 0:
        return None
    commits = scrape.delta(ctx.before, ctx.after, "dli_diffusion_row_forwards_total",
                           kind="commit")
    return 100.0 * commits / total
