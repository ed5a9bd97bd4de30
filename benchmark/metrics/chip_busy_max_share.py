"""Share of the traced window in which the busiest chip ran an
operation: beside the mean (`device_idle_share`), the skew between the
chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy"] or t["window_s"] <= 0:
        return None
    return max(sum(e - s for s, e in iv)
               for iv in t["busy"].values()) / t["window_s"]
