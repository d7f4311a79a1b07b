"""Model step: the share of the device time inside the traced step programs
that runs under one of the program's scopes (`utils/tracing.STEP_SCOPES`,
joined to the trace by harness/program_scopes.py). What is left is what no
scope owns: the layer scan's own slices, loop counters, the packed fetch,
whole-stack relayouts, and any step module the session's map does not hold."""
from harness import program_scopes


def read(ctx):
    got = program_scopes.read(ctx)
    if got is None:
        return None
    total = sum(mod["seconds"] for mod in got["modules"].values())
    scoped = sum(s for mod in got["modules"].values()
                 for key, s in mod["by_scope"].items() if key)
    return 100.0 * scoped / total if total > 0 else None
