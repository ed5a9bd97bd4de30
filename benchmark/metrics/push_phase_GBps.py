"""GB/s of a job's push phase: the bytes of the window's
`ec.distribute` spans over their `pushSeconds` (first push's start to
last push's end, the streams to the targets running at once), where
`push_GBps` reads one stream's rate."""

from benchmark import job_trace


def read(ctx):
    dist = [s for s in job_trace.named(ctx, "ec.distribute")
            if "pushSeconds" in (s.get("attrs") or {})]
    sent = job_trace.attr_sum(dist, "bytes")
    took = job_trace.attr_sum(dist, "pushSeconds")
    return sent / took / 1e9 if sent and took else None
