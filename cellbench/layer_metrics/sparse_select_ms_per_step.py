"""Model step: device time of the sparse attention layers' selection
(`sparse_select`, nested under `attn`: the compressed-key write, the scores
of the queries against the row's compressed keys, softmax, the sum over a
KV head's query heads, the block scores, top-k and the page lists) per
scheduler step. `attn_layer_ms_per_step` holds it too: the label nests
under `attn`, and that reader counts by the outermost. From a program
without the scope, None."""
from harness import program_scopes, trace_reduce

LABEL = "sparse_select"


def read(ctx):
    got = program_scopes.read(ctx)
    steps = trace_reduce.step_durations(ctx)
    if got is None or not steps:
        return None
    seconds = [s for mod in got["modules"].values()
               for key, s in mod["by_scope"].items() if LABEL in key.split("/")]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / len(steps)
