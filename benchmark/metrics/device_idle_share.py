"""Share of the traced window in which no operation ran on the device."""

from benchmark import trace_reduce


def read(ctx):
    t = ctx["trace"]
    return trace_reduce.idle_share(t["busy_s"], t["window_s"]) if t \
        else None
