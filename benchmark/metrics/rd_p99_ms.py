"""The 99th percentile, in milliseconds, of every request the clients
sent in the window, a failed or wrong one at the larger of what it
took and the request timeout (`ctx["reads"]`, the load children's own
records): the tail the serving planes kept while the jobs ran."""


def read(ctx):
    return ctx["reads"]["read_p99_ms"] if ctx.get("reads") else None
