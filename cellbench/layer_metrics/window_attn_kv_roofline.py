"""Kernels: the least time the chip needs to read the keys and values the
traced launches' rows attend ((`kv_tokens_global` x the global layers +
`kv_tokens_window` x the window layers of the file's `layer_types`) x 2 x
8 x 128 x 2 B at the peak HBM bandwidth) or to compute the useful
score-and-value operations over them at the bf16 peak, the larger
(roofline/windowed_attention.py), over the two attention kernels' device
time in those launches. For a configuration with window and global layers
served by a program whose launch record counts each kind; from any other
(the parent commit, a model of one kind of layer), or from a trace without
the launch spans, None. A prefill chunk's prefix is counted once where the
kernel reads it per 8-token query tile: a lower bound, so it understates,
most in mixed steps."""
from harness import manifest


def read(ctx):
    if "sliding_attention" not in ctx.config.get("layer_types", ()):
        return None
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "attention_kernels")
    if got is None or got[1] <= 0:
        return None
    launches = [launch for launch, _ in got[0] if "kv_tokens_window" in launch]
    if not launches:
        return None
    windowed = manifest.load_module("roofline", "windowed_attention")
    positions = sum(windowed.positions(ctx.config, launch) for launch in launches)
    return 100.0 * windowed.bound(ctx.config, positions, ctx.peaks)[0] / got[1]
