"""The median, in milliseconds, of every request the clients sent in
the window (`ctx["reads"]`, the load children's own records): with
closed-loop clients the rate's other reading, kept beside the tail."""


def read(ctx):
    return ctx["reads"]["read_p50_ms"] if ctx.get("reads") else None
