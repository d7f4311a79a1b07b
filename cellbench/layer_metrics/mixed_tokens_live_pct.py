"""Model step: of the flat tokens the window's mixed steps COMPUTED (the axis
their token-wise layers ran on: every product, norm, convolution and
within-launch sum), the share that were live: a decode row's token, a verify
row's, a prompt chunk's (`dli_mixed_tokens_total{state="live"}` over
`{state="computed"}`, counted at the launch). The rest is padding the launch's
shape made the model compute: a decode row's tile of 8 holds one live token.
Where a launch is the fleet's tiles plus prefill's budget
(`engine/scheduler.live_width`: granite-4.0-h-micro at 64 slots) the program
packs the live tokens and computes 320 of a 640-token launch; elsewhere the
computed axis is the launch's width (`olmo2-batch`: 128 on both sides of a
comparison). No mixed launch in the window, or a program without the counter
(a commit before PR 54): None."""
from harness import scrape

NAME = "dli_mixed_tokens_total"


def read(ctx):
    computed = scrape.delta(ctx.before, ctx.after, NAME, state="computed")
    if computed <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, NAME, state="live") / computed
