"""Share of the staged windows' h2d seconds that was the host copy
into the staging buffer (`np.copyto`), not the put and its fence: the
staging ledger's `pack_seconds` over its `h2d_seconds`."""


def read(ctx):
    s = ctx["staging"]
    if "pack_seconds" not in s or s.get("h2d_seconds", 0) <= 0:
        return None
    return s["pack_seconds"] / s["h2d_seconds"]
