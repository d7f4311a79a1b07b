"""Routed experts: device time of the whole routed layer per scheduler step:
the router with the norm in front of it, sort and gather (`moe_dispatch`),
the grouped kernels (`moe_experts`: what `moe_ms_per_step` counts), the
weighted sum back (`moe_combine`) and the shared expert (`moe_shared`)."""
from harness import program_scopes

LABELS = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared")


def read(ctx):
    return program_scopes.ms_per_step(ctx, LABELS)
