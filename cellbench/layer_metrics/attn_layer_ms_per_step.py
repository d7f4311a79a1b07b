"""Model step: device time of the attention blocks per scheduler step: every
instruction under the `attn` scope (input norm, projections, qk-norm,
rotary, the paged kernel with its K/V write, `mla_absorb` in the latent
family, the output projection), where `attn_kernel_ms_per_step` is the
kernel alone."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("attn",))
