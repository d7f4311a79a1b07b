"""Model step: the device's time a MIXED step (decode rows and prefill chunks
in one launch), over the whole window and with the profiler off:
`decode_step_ms_mean`'s quotient for `phase="mixed"` (a mixed launch is one
step). A window with no timed mixed launch: None."""
from harness import manifest


def read(ctx):
    return manifest.load_module("layer_metrics", "decode_step_ms_mean").step_ms(ctx, "mixed")
