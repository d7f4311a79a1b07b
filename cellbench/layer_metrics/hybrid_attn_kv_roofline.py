"""Kernels: the least time the chip needs to read the keys and values the
traced launches' rows attend (`kv_tokens` x 2 x 8 x 64 x 2 B x the
ATTENTION layers of the file's `layer_types`, at the peak HBM bandwidth) or
to compute the useful score-and-value operations over them at the bf16
peak, the larger (roofline/hybrid_attention.py), over the attention
kernels' device time in those launches. For a configuration whose layers
are not all attention layers; from any other, or from a program or a trace
without the launch spans, None. A prefill chunk's prefix is counted once
where the kernel reads it per 8-token query tile, and the zero lanes of
the packed head-dim-64 layout are not counted: a lower bound, so it
understates, most in mixed steps."""
from harness import manifest


def read(ctx):
    if "layer_types" not in ctx.config:
        return None
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "attention_kernels")
    if got is None or got[1] <= 0:
        return None
    hybrid = manifest.load_module("roofline", "hybrid_attention")
    tokens = sum(int(launch["kv_tokens"]) for launch, _ in got[0])
    return 100.0 * hybrid.bound(ctx.config, tokens, ctx.peaks)[0] / got[1]
