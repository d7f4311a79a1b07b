"""Kernels: of the row-steps whose float32 matrix state the delta rule read
and wrote in the window, the share that rode a DECODE CHUNK
(`dli_delta_state_rows_total{phase="chunk"}` over both phases, counted at the
launch): one token a row by construction, which the decode program serves in
the rule's one-token form (`ops/delta_rule.delta_rule_step`: the recurrence
on the vector unit). The rest rode a mixed launch, a prompt chunk's row or a
decode row beside one, and went through the chunked form. So it is the share
of the state's trips that mechanism can reach, and the parent of the PR that
brought it (58) reads the same share from the same counter. No state row in
the window, or a program without the counter (a configuration without
delta-rule layers: the series are made with the engine and stay at 0): None."""
from harness import scrape

NAME = "dli_delta_state_rows_total"


def read(ctx):
    rows = scrape.delta(ctx.before, ctx.after, NAME)
    if rows <= 0:
        return None
    return 100.0 * scrape.delta(ctx.before, ctx.after, NAME, phase="chunk") / rows
