"""Model step: the least time the chip needs to stream the step's weights
(cellbench/roofline/weights.py, bytes / peak HBM bandwidth) over the step's
median device time. Bandwidth-bound, and a lower bound on the work: it
leaves out KV and activations, so it understates the share."""
from harness import manifest, stats, trace_reduce


def read(ctx):
    steps = trace_reduce.step_durations(ctx)
    if not steps:
        return None
    weights = manifest.load_module("roofline", "weights")
    least = weights.step_weight_bytes(ctx.config) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / stats.percentile(steps, 50, enforce=False)
