"""Mean milliseconds a client took between a response and its next
send (digest of the body included): the generator's own cost."""


def read(ctx):
    return ctx["reads"]["late_mean_ms"] if ctx["reads"] else None
