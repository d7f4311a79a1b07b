"""Continuous engine: the worker thread's own time per scheduler step: the
delta of `dli_worker_phase_seconds_total` over every phase but the two in
which it waits (`fetch_wait` on the chip, `wait_work` on a request), over
the window's steps (mixed_step_pct.steps). Planning, block tables,
dispatch, distributing tokens, streaming, detokenising: what a step would
cost if the chip took no time."""
from harness import manifest, scrape

WAITING = ("fetch_wait", "wait_work")


def read(ctx):
    n = manifest.load_module("layer_metrics", "mixed_step_pct").steps(ctx)
    if not n or n[1] <= 0:
        return None
    busy = scrape.delta(ctx.before, ctx.after, "dli_worker_phase_seconds_total") - sum(
        scrape.delta(ctx.before, ctx.after, "dli_worker_phase_seconds_total", phase=p)
        for p in WAITING)
    return 1e3 * busy / n[1]
