"""Share of the jobs' wall time spent pulling the volume and pushing
the shards: (pull + distribute) over job wall, summed over the window's
jobs, from the worker's progress reports."""


def read(ctx):
    copy = wall = 0.0
    for j in ctx["jobs"]:
        ph = j.get("phases", {})
        if "pull" in ph and "distribute" in ph and j.get("end"):
            copy += sum(e - s for s, e in (ph["pull"], ph["distribute"]))
            wall += j["end"] - j["start"]
    return copy / wall if wall > 0 else None
