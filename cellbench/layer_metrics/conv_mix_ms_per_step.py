"""Model step: device time of the gated short-convolution operators
(`conv_mix`: the norm in front, in-projection, taps over the row's state,
out-projection, the state and tail writes) per scheduler step."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("conv_mix",))
