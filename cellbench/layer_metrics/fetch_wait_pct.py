"""Continuous engine: share of the window's wall time the worker thread
spent in the blocking fetch of a launch's results (`phase="fetch_wait"` of
`dli_worker_phase_seconds_total`): near 100 while the chip sets the pace.
The whole-window, host-side twin of `device_idle_pct`'s 4 s."""
from harness import manifest, scrape


def read(ctx):
    if not manifest.load_module("layer_metrics", "mixed_step_pct").counts_chunks(ctx):
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, "dli_worker_phase_seconds_total",
                                phase="fetch_wait") / ctx.window_s
