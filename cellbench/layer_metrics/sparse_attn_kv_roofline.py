"""Kernels: the least time the chip needs to read the keys and values the
traced launches' rows SELECTED (`kv_tokens` of a fleet with sparse attention
layers counts what is read: x 2 x num_key_value_heads x head_dim x 2 B x the
`minicpm4` layers of `mixer_types`, at the peak HBM bandwidth) or to compute
the useful score-and-value operations over them at the bf16 peak, the
larger (roofline/sparse_attention.py), over the two paged kernels' device
time in those launches. From a configuration without `sparse_config`, or a
program or a trace without the launch spans or the record's
`kv_tokens_visible`, None. A prefill chunk's selection is counted once (its
last query's) where the kernel walks the union of a tile's choices per
8-token tile: a lower bound, so it understates, most in mixed steps."""
from harness import manifest


def read(ctx):
    if "sparse_config" not in ctx.config:
        return None
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "attention_kernels")
    if got is None or got[1] <= 0:
        return None
    launches = [launch for launch, _ in got[0] if "kv_tokens_visible" in launch]
    if not launches:
        return None
    sparse = manifest.load_module("roofline", "sparse_attention")
    tokens = sum(int(launch["kv_tokens"]) for launch in launches)
    return 100.0 * sparse.bound(ctx.config, tokens, ctx.peaks)[0] / got[1]
