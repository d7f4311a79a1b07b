"""HTTP + admission: time to first token (due time -> first stream event),
median over the window's requests. What a user feels first; recorded here
without a bound because it swings by 13% from seed to seed (whether an
arrival falls early or late in a 0.9 s decode chunk decides its wait), too
wide for any bound the contract admits (PERF.md section 2)."""
from harness import stats


def read(ctx):
    ttft = [(r.first - r.due) * 1e3 for r in ctx.ok]
    if ctx.closed or len(ttft) < stats.min_samples(50):
        return None
    return stats.percentile(ttft, 50)
