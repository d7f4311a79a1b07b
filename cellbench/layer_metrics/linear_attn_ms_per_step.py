"""Model step: device time of the decayed linear-attention mixers
(`linear_attn`: the norm in front, the projections, qk-norm, rotary, the
chunked scan or the recurrence over the float32 matrix state, the state and
snapshot writes, output norm, gate and `W_o`) per scheduler step. From a
program without the scope, None."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("linear_attn",))
