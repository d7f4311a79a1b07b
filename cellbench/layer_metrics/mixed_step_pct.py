"""Continuous engine: share of the window's scheduler steps that were mixed
steps (decode rows + prefill chunks in one launch), the rest being the steps
of pure-decode chunks: `dli_ragged_launches_total{phase}`, a mixed launch
one step and a chunk launch --continuous-chunk steps. The program counts
both kinds of launch since PR 24; from an older one (no
`dli_worker_phase_seconds_total` on its /metrics) None."""
from harness import scrape


def counts_chunks(ctx) -> bool:
    return any(name == "dli_worker_phase_seconds_total" for name, _ in ctx.after)


def steps(ctx):
    """(mixed steps, all steps) of the window, or None."""
    if not counts_chunks(ctx):
        return None
    mixed = scrape.delta(ctx.before, ctx.after, "dli_ragged_launches_total", phase="mixed")
    chunk = scrape.delta(ctx.before, ctx.after, "dli_ragged_launches_total", phase="chunk")
    return mixed, mixed + ctx.chunk_steps * chunk


def read(ctx):
    n = steps(ctx)
    return 100.0 * n[0] / n[1] if n and n[1] > 0 else None
