"""Paged KV: the least free share of the pool seen in the window's 1 Hz
scrapes (`dli_kv_pool_blocks_free` / pool blocks). Memory reserved and
unused limits the batch, and so the throughput."""
from harness import scrape


def read(ctx):
    frees = [scrape.total(s, "dli_kv_pool_blocks_free") for s in ctx.scrapes]
    if not frees or ctx.pool_blocks <= 0:
        return None
    return 100.0 * min(frees) / ctx.pool_blocks
