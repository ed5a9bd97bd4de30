"""Seconds a job's consumer stood blocked on the next staged window
(`ready_wait_seconds` per finished job): the staging thread — pack,
put, fence — is the slower side of the hand-off."""


def read(ctx):
    done = sum(j["ok"] for j in ctx["jobs"])
    s = ctx["staging"]
    return s["ready_wait_seconds"] / done \
        if "ready_wait_seconds" in s and done else None
