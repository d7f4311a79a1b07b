"""Continuous engine: rows that read and wrote their recurrent states per
scheduler step, over the traced launches: the `launch.*` spans'
`state_rows` (a decode row a step, a prefill chunk once) over their `steps`
(1 of a mixed step, the chunk's of a decode chunk). How full the fleet
really runs: each such row-step moves its states both ways, so this times
the state's bytes is the stream a step cannot avoid. From a program or a
trace without the spans or the record's `state_rows`, None."""
from harness import host_spans


def read(ctx):
    path = host_spans.find(ctx.trace_dir)
    if path is None:
        return None
    launches = [st for name, _, _, st in host_spans.read(path)
                if name.startswith("launch.") and "state_rows" in st]
    steps = sum(int(st.get("steps", 1)) for st in launches)
    if steps <= 0:
        return None
    return sum(int(st["state_rows"]) for st in launches) / steps
