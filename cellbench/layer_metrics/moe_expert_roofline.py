"""Routed experts: per traced launch the least time the chip needs for the
experts it touched (the larger of their matrices' bytes at the peak HBM
bandwidth and the token-expert pairs' operations at the bf16 peak:
roofline/moe_experts.py), summed, over the device time of the grouped
matrix product kernels (`serving.trace.expert_kernels`) in those launches.
What a launch routed comes from the program: `moe_experts_touched` (experts
x layers x steps that got a token) and `moe_pairs`, on the span that
follows the launch's fetch. Lower bounds both, so it understates."""
from harness import manifest


def read(ctx):
    got = manifest.load_module("roofline", "traced_launches").read(ctx, "expert_kernels")
    if got is None or got[1] <= 0:
        return None
    moe = manifest.load_module("roofline", "moe_experts")
    routed = [after for _, after in got[0] if "moe_pairs" in after]
    if not routed:
        return None
    least = sum(moe.bound(ctx.config, int(a["moe_experts_touched"]), int(a["moe_pairs"]),
                          ctx.peaks)[0] for a in routed)
    return 100.0 * least / got[1]
