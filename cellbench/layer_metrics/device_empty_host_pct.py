"""Continuous engine: share of the window in which the device's queue stood
empty while the worker was in any phase but `wait_work` (reaping, admitting,
planning, dispatching, distributing a fetch's tokens): the chip waited for
Python (`dli_device_empty_seconds_total` less its `wait_work` child, over
the window). A program without the counter: None."""
from harness import manifest


def read(ctx):
    return manifest.load_module("layer_metrics", "device_empty_wait_pct").empty_pct(
        ctx, waiting=False)
