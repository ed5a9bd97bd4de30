"""GB/s of the shard pushes: the bytes of the window's `ec.push` spans
over the sum of their seconds (one span a pushed file): one stream's
rate where a job's pushes run on several streams at once;
`push_phase_GBps` has the phase's."""

from benchmark import job_trace


def read(ctx):
    push = job_trace.named(ctx, "ec.push")
    sent, took = job_trace.attr_sum(push, "bytes"), job_trace.seconds(push)
    return sent / took / 1e9 if sent and took > 0 else None
