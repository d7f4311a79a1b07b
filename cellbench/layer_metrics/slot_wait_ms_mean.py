"""HTTP + admission: mean wait from enqueue until a slot and pool blocks were
granted, over the requests whose first token came in the window: the delta
of the program's `dli_queue_wait_seconds` histogram (sum / count). With
`prefill_ms_mean` it is the two halves of `queue_wait_ms_mean`, which is
enqueue -> first token on the server. None from a program without it."""
from harness import scrape


def mean(ctx, histogram: str, scale: float = 1.0, **labels):
    """Mean of what a histogram observed in the window, or None."""
    n = scrape.delta(ctx.before, ctx.after, histogram + "_count", **labels)
    if n <= 0:
        return None
    return scale * scrape.delta(ctx.before, ctx.after, histogram + "_sum", **labels) / n


def read(ctx):
    return mean(ctx, "dli_queue_wait_seconds", 1e3)
