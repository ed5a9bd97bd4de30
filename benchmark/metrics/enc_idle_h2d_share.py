"""Of the seconds the device sat idle inside the jobs' `ec.encode`
spans, the share during which a staged window was on its way in (an
open `stage.h2d` span: pack, put, fence).  The device's busy intervals
come from the device trace on the host's clock (the harness ties them
to it by its sync marks), the spans from the program on the same
clock.  What is left of the idle time is the reader, the sinks and the
fetch: nothing was being sent."""

from benchmark import job_trace, trace_reduce


def read(ctx):
    if not ctx["trace"]:
        return None
    encode = job_trace.named(ctx, "ec.encode")
    h2d = job_trace.named(ctx, "stage.h2d")
    if not encode or not h2d:
        return None

    def window(s):
        return s["start"], s["start"] + s["durationMs"] / 1e3
    busy = trace_reduce.union(
        [iv for ivs in ctx["trace"]["busy"].values() for iv in ivs])
    sending = trace_reduce.union([window(s) for s in h2d])
    idle_s = under_s = 0.0
    for lo, hi in map(window, encode):
        at, idle = lo, []
        for s, e in trace_reduce.clip(busy, lo, hi):
            if s > at:
                idle.append((at, s))
            at = max(at, e)
        if hi > at:
            idle.append((at, hi))
        idle_s += sum(e - s for s, e in idle)
        under_s += sum(min(e, b) - max(s, a) for s, e in idle
                       for a, b in sending if min(e, b) > max(s, a))
    return under_s / idle_s if idle_s > 0 else None
