"""Model step: device time of the state-space mixers (`ssm_mix`: the norm in
front, the input projection, the causal convolution with its state, dt, the
scan over the float32 matrix state (`ssm_scan` nests here), the state and
snapshot writes, the gated norm and `W_out`) per scheduler step. From a
program without the scope, None."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("ssm_mix",))
