"""Share of the window's seconds in which a background job was in
flight (submit to finish by the harness's clock, one job at a time, cut
to the window): what the servers did for the jobs beside the readers.
The burst is a fixed number of jobs, so it falls when the encode gets
faster, which is why the readers' numbers then move."""


def read(ctx):
    if not ctx["jobs"]:
        return None
    lo, hi = ctx["window"]["open"], ctx["window"]["end"]
    busy = sum(max(0.0, min(j["finish"], hi) - max(j["submit"], lo))
               for j in ctx["jobs"])
    return busy / (hi - lo)
