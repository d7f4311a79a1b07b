"""Model step: device time of the delta-rule mixers (`delta_mix`: the norm in
front, the input projection, the three causal convolutions with their state
(`delta_conv` nests here), decay and beta, the delta rule over the float32
matrix state (`delta_scan` nests here: the chunk algebra and the carried
state's program), the state and snapshot writes, the head norm, the gate and
`W_o`) per scheduler step. From a program without the scope, None."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("delta_mix",))
