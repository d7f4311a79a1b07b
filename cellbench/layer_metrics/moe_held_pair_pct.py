"""Routed experts: of the live token-expert pairs the routers chose in the
window, the share whose expert lives on this chip
(`dli_moe_pairs_total{where="held"}` over `{where="routed"}`): 12.5 where a
chip holds an eighth of the experts and routing is uniform, 100 where every
expert is held. The roofline of the expert kernels is of the held pairs
alone; this says what share of the layer's work that is. From a program
without the counter None."""
from harness import scrape


def read(ctx):
    routed = scrape.delta(ctx.before, ctx.after, "dli_moe_pairs_total", where="routed")
    if routed <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, "dli_moe_pairs_total", where="held") / routed
