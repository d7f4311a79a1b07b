"""Paged KV + prefix: share of the window's prompt tokens served from the
block-prefix index (sum of `prefix_cached_tokens` over sum of
`prompt_tokens` of the window's result envelopes). About 0 where prompts
share nothing: the bypass."""


def read(ctx):
    total = sum(r.prompt_tokens for r in ctx.ok)
    if total <= 0:
        return None
    return 100.0 * sum(r.cached_tokens for r in ctx.ok) / total
