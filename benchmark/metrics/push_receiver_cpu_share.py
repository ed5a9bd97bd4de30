"""Share of the pushes' wall time in which the receiving volume
server's handler thread was on a CPU: `cpuSeconds` of the volume roles'
`POST /admin/receive_file` server spans in the jobs' traces, over the
seconds of the `ec.push` spans they hang under."""

from benchmark import job_trace


def read(ctx):
    took = job_trace.seconds(job_trace.named(ctx, "ec.push"))
    cpu = job_trace.attr_sum(job_trace.named(
        ctx, "POST /admin/receive_file", role="volume"), "cpuSeconds")
    return cpu / took if cpu is not None and took > 0 else None
