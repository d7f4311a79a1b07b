"""Load generator: the latest any of the window's requests was sent after
it was due (sent - due, the generator's own clock). A starved generator must
not be read as a fast server."""


def read(ctx):
    if ctx.closed or not ctx.late_ms:
        return None
    return max(ctx.late_ms)
