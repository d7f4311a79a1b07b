"""Kernels: the least time the chip needs to read the latent rows the traced
launches' rows attend (`kv_tokens` x 1,152 B x layers at the peak HBM
bandwidth) or to compute the absorbed form's operations over them at the
bf16 peak, the larger (roofline/mla_attention.py), over the attention
kernels' device time in those launches. The count takes the 576 numbers of
a row that carry data, not the 640 stored, and a prefill chunk's prefix
once where the kernel reads it per 8-token query tile: a lower bound, so it
understates, most in mixed steps."""
from harness import manifest


def read(ctx):
    if "kv_lora_rank" not in ctx.config:
        return None
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "attention_kernels")
    if got is None or got[1] <= 0:
        return None
    mla = manifest.load_module("roofline", "mla_attention")
    tokens = sum(int(launch["kv_tokens"]) for launch, _ in got[0])
    return 100.0 * mla.bound(ctx.config, tokens, ctx.peaks)[0] / got[1]
