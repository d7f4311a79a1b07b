"""HTTP + admission: the tail of time to first token (due time -> first
stream event), 90th percentile over the window's requests. A window holds
some tens of requests, fewer than the hundred a 90th percentile wants (ten
samples beyond it), so it is recorded here without a bound and not judged."""
from harness import stats


def read(ctx):
    ttft = [(r.first - r.due) * 1e3 for r in ctx.ok]
    if ctx.closed or len(ttft) < stats.min_samples(50):
        return None
    return stats.percentile(ttft, 90, enforce=False)
