"""Correct responses the clients got inside the window, a second,
while the encode jobs ran beside them: what the serving planes gave
their clients with the maintenance plane at work (`ctx["reads"]`, the
load children's own records)."""


def read(ctx):
    return ctx["reads"]["read_rps"] if ctx.get("reads") else None
