"""Kernels: the least time the chip needs to read the keys and values the
traced launches' rows attend, EACH KIND OF LAYER BY ITS OWN K/V HEADS and by
the 192 + 128 useful lanes of a key and a value (`kv_tokens_global` x the
global layers x 4 heads + `kv_tokens_window` x the window layers x 8 heads,
x 320 numbers x 2 B at the peak HBM bandwidth), or to compute the useful
score-and-value operations over them at the bf16 peak, the larger
(roofline/window_sink_attention.py), over the two paged kernels' device time
in those launches. The 64 zero lanes of a stored key row are lost share, not
work. For a configuration that names `hybrid_layer_pattern` and the window
layers' own K/V heads, served by a program whose launch record counts each
kind; from any other, or from a trace without the launch spans, None. A
prefill chunk's prefix is counted once where the kernel reads it per 8-token
query tile: a lower bound, so it understates, most in mixed steps."""
from harness import manifest


def read(ctx):
    roofline = manifest.load_module("roofline", "window_sink_attention")
    if roofline.sizes(ctx.config) is None:
        return None
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "attention_kernels")
    if got is None or got[1] <= 0:
        return None
    least = roofline.bound(ctx.config, [launch for launch, _ in got[0]], ctx.peaks)
    return None if least is None else 100.0 * least[0] / got[1]
