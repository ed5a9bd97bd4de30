"""Mean milliseconds a volume server spent on a GET in the window: the
delta of the volume roles' request_seconds histogram, sum over count."""


def read(ctx):
    v = ctx["volume_counters"]
    return v["req_s"] / v["req_n"] * 1e3 if v and v["req_n"] > 0 else None
