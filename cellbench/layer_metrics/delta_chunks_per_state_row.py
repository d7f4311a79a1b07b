"""Continuous engine: chunks of the delta rule a state's trip through the
program serves, over the traced launches: the `launch.*` spans'
`delta_chunks` (the chunks of 64 flat places their rows' tokens were cut
into) over their `state_rows` (the row-steps that read and wrote a state). 1
where every row decodes; up to 8 where a 512-token launch is one row's
prompt: the higher, the more tokens each float32 state's read and write is
shared among. From a program or a trace without the spans or the record's
`delta_chunks`, None."""
from harness import host_spans


def read(ctx):
    path = host_spans.find(ctx.trace_dir)
    if path is None:
        return None
    launches = [st for name, _, _, st in host_spans.read(path)
                if name.startswith("launch.") and "delta_chunks" in st]
    rows = sum(int(st.get("state_rows", 0)) for st in launches)
    if rows <= 0:
        return None
    return sum(int(st["delta_chunks"]) for st in launches) / rows
