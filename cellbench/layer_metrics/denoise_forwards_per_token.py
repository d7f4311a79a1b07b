"""Block diffusion: forwards of the model a row spent per token it
delivered, over the window: `dli_diffusion_row_forwards_total` (both kinds:
denoise forwards reveal masked positions of the row's open block, a commit
forward writes the clean block's K/V and emits it) over
`dli_diffusion_tokens_total`. An autoregressive row spends 1; a block of 4
revealed by denoise_steps forwards and committed by one more spends
(denoise_steps + 1) / 4. From a program without the counters None."""
from harness import scrape


def read(ctx):
    tokens = scrape.delta(ctx.before, ctx.after, "dli_diffusion_tokens_total")
    if tokens <= 0:
        return None
    return scrape.delta(ctx.before, ctx.after, "dli_diffusion_row_forwards_total") / tokens
