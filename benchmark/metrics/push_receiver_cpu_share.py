"""Share of the pushes' wall time in which the receiving volume
server's handler thread was on a CPU: `cpuSeconds` of the volume roles'
`POST /admin/receive_file` server spans in the jobs' traces, over the
seconds of the `ec.push` spans they hang under.  A volume role's span
ring rolls over before the traces are fetched, so only the pushes whose
receiver span was found count on either side; nothing where none was."""

from benchmark import job_trace


def read(ctx):
    recv = job_trace.named(ctx, "POST /admin/receive_file", role="volume")
    found = {s.get("parentId") for s in recv}
    took = job_trace.seconds([s for s in job_trace.named(ctx, "ec.push")
                              if s["spanId"] in found])
    cpu = job_trace.attr_sum(recv, "cpuSeconds")
    return cpu / took if cpu is not None and took > 0 else None
