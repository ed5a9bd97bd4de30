"""Seconds of the `ec.encode` span, mean over the window's jobs."""


def read(ctx):
    d = [s["durationMs"] / 1e3 for j in ctx["jobs"] for s in j["spans"]
         if s["name"] == "ec.encode"]
    return sum(d) / len(d) if d else None
