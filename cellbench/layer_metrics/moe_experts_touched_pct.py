"""Routed experts: share of the window's expert slots (expert layers x
experts x scheduler steps) in which the expert got at least one token:
`dli_moe_experts_touched_total` over `dli_moe_expert_slots_total`. A mixed
step's prefill chunk reaches nearly every expert, a decode chunk's few rows
a minority: the share says how much of the expert banks a step reads. From
a program without the counters None."""
from harness import scrape


def read(ctx):
    slots = scrape.delta(ctx.before, ctx.after, "dli_moe_expert_slots_total")
    if slots <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, "dli_moe_experts_touched_total") / slots
