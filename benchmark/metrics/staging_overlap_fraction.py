"""How much of the staging's possible overlap of copy-in, kernel and
copy-out was had, byte-weighted over the window's launches."""


def read(ctx):
    s = ctx["staging"]
    if s.get("overlap_denom", 0) <= 0:
        return None
    return s["overlap_numer"] / s["overlap_denom"]
