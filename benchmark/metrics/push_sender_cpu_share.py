"""Share of the pushes' wall time in which the worker's pushing thread
was on a CPU: `cpuSeconds` (its `thread_time()` delta) over the seconds
of the window's `ec.push` spans.  Near 1, the sender's Python is the
push; near 0, the sender waits on the receiver or the socket."""

from benchmark import job_trace


def read(ctx):
    push = job_trace.named(ctx, "ec.push")
    cpu, took = job_trace.attr_sum(push, "cpuSeconds"), \
        job_trace.seconds(push)
    return cpu / took if cpu is not None and took > 0 else None
