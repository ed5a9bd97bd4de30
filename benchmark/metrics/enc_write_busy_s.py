"""Busy seconds of the `encode.write` stage (the shard sinks), mean
over the window's jobs."""


def read(ctx):
    d = [s["busySeconds"] for j in ctx["jobs"] for s in j["spans"]
         if s["name"] == "encode.write" and s["busySeconds"] is not None]
    return sum(d) / len(d) if d else None
