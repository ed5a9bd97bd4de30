"""Seconds a job's staging thread stood blocked on a free slot
(`slot_wait_seconds` per finished job): the consumer — fetch and shard
sinks — is the slower side of the hand-off."""


def read(ctx):
    done = sum(j["ok"] for j in ctx["jobs"])
    s = ctx["staging"]
    return s["slot_wait_seconds"] / done \
        if "slot_wait_seconds" in s and done else None
