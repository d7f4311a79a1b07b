"""Model step: device time of the dense feed-forward blocks (`ffn`: the norm
in front, gate, up, down) per scheduler step; in a routed configuration the
leading dense layers alone."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("ffn",))
