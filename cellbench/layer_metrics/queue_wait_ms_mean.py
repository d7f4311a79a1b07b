"""HTTP + admission: mean enqueue-to-dispatch wait over the window, from
the program's `dli_admission_wait_seconds` histogram (sum / count, as the
difference of two scrapes)."""
from harness import scrape


def read(ctx):
    n = scrape.delta(ctx.before, ctx.after, "dli_admission_wait_seconds_count")
    if n <= 0:
        return None
    return 1e3 * scrape.delta(ctx.before, ctx.after, "dli_admission_wait_seconds_sum") / n
