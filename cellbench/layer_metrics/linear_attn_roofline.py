"""Kernels: the least time the chip needs for the linear-attention scan of
the traced launches (each row-step's float32 state read and written plus its
tokens' q, k, v, o at the peak HBM bandwidth, or the state and within-chunk
operations at the bf16 peak, the larger: roofline/linear_attention.py) over
the device time under the `linear_scan` scope (nested in `linear_attn`: the
chunked scan of a mixed step, the recurrence of a decode step) in the
trace's step programs. The launches counted are those the trace matches
with a span; the time is every traced execution's, so the share can only
understate. From a configuration without `lightning_nh`, or a program or a
trace without the scope or the record's `state_rows`, None."""
from harness import manifest, program_scopes

LABEL = "linear_scan"


def read(ctx):
    if "lightning_nh" not in ctx.config:
        return None
    scopes = program_scopes.read(ctx)
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "attention_kernels")
    if scopes is None or got is None:
        return None
    seconds = sum(s for mod in scopes["modules"].values()
                  for key, s in mod["by_scope"].items() if LABEL in key.split("/"))
    launches = [launch for launch, _ in got[0] if "state_rows" in launch]
    if seconds <= 0 or not launches:
        return None
    linear = manifest.load_module("roofline", "linear_attention")
    return 100.0 * linear.bound(ctx.config, launches, ctx.peaks)[0] / seconds
