"""Seconds of the `ec.distribute` span (placement, every push, every
mount), mean over the window's jobs: the program's own span."""

from benchmark import job_trace


def read(ctx):
    d = job_trace.named(ctx, "ec.distribute")
    return job_trace.seconds(d) / len(d) if d else None
