"""Share of the pushed bytes that were sent out of the `.dat` the job
pulled and not out of a shard file the worker wrote first: the bytes of
the window's `ec.push` spans whose `source` is "dat" over the bytes of
all of them.  10 of 14 shards (0.714) where the encode writes the
parity alone; 0 where no push says where its bytes came from (the
program before PR 35) or every one says "file" (a batch job); nothing
where no push was found at all."""

from benchmark import job_trace


def read(ctx):
    push = job_trace.named(ctx, "ec.push")
    sent = job_trace.attr_sum(push, "bytes")
    if not sent:
        return None
    return sum(s["attrs"]["bytes"] for s in push
               if s["attrs"].get("source") == "dat") / sent
