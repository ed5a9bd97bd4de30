"""Host-to-device GB/s of the staged windows sent in the window: the
staging ledger's bytes over its seconds.  The bytes are what the
program sent, padding included (`staging_launch_ratio` says how much of
it is padding): a rate of the link, not of volume bytes."""


def read(ctx):
    s = ctx["staging"]
    if s.get("h2d_seconds", 0) <= 0:
        return None
    return s["h2d_bytes"] / s["h2d_seconds"] / 1e9
