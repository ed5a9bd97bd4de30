"""Bytes the staging layer sent to the device over the `.dat` bytes of
the window's jobs: 1.0 were nothing padded; what lies above is window
and column padding that the link and the kernel work through for no
volume byte."""


def read(ctx):
    dat = sum(j["bytes"] for j in ctx["jobs"] if j["ok"])
    sent = ctx["staging"].get("h2d_bytes", 0)
    return sent / dat if dat > 0 and sent > 0 else None
