"""Continuous engine: mean time from the grant of a slot and pool blocks to
the fetch of the request's first token (chunked prefill, sharing its steps
with the decode rows, behind whatever was dispatched ahead of its first
chunk): the delta of the program's `dli_prefill_seconds` histogram."""
from harness import manifest


def read(ctx):
    return manifest.load_module("layer_metrics", "slot_wait_ms_mean").mean(
        ctx, "dli_prefill_seconds", 1e3)
