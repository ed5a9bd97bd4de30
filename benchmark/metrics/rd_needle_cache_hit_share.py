"""Share of the volume servers' needle lookups in the window that the
hot-needle cache answered (hits over hits + misses, the three servers
summed): how much of the read path below it the window's reads skipped."""


def read(ctx):
    v = ctx["volume_counters"]
    if not v or v["cache_hits"] + v["cache_misses"] <= 0:
        return None
    return v["cache_hits"] / (v["cache_hits"] + v["cache_misses"])
