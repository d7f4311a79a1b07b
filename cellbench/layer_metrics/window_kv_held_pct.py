"""Paged KV + prefix: the window group's blocks held (by rows and by the
prefix index: `dli_kv_group_blocks{group="window"}`, live + cached) over the
global group's, mean of the window's 1 Hz scrapes. A block of either group
holds the same positions, so this is the share of the cached and live
context that the window layers still keep: 100 if they kept everything
(no allocator per layer kind), about sliding_window over the mean context
where they give back what they can no longer read. From a program without
the gauges, or a pool of one group, None."""
from harness import scrape


def read(ctx):
    shares = []
    for s in ctx.scrapes:
        held = {group: scrape.total(s, "dli_kv_group_blocks", group=group, state="live")
                + scrape.total(s, "dli_kv_group_blocks", group=group, state="cached")
                for group in ("global", "window")}
        known = any(n == "dli_kv_group_blocks" and ("group", "window") in key for n, key in s)
        if known and held["global"] > 0:
            shares.append(100.0 * held["window"] / held["global"])
    return sum(shares) / len(shares) if shares else None
