"""Continuous engine: scheduler steps already dispatched and not yet fetched
when a mixed step (the launches that carry prefill chunks) was dispatched,
mean over the window's mixed launches: the delta of the program's
`dli_launch_steps_ahead{phase="mixed"}` histogram. A decode chunk ahead is
--continuous-chunk steps of device work a new arrival's first prompt tokens
wait behind, whatever the queue."""
from harness import manifest


def read(ctx):
    return manifest.load_module("layer_metrics", "slot_wait_ms_mean").mean(
        ctx, "dli_launch_steps_ahead", phase="mixed")
