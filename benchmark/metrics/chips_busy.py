"""Chips on which any operation ran inside the traced window: says at
a glance whether the program's placement used the chips the cell
holds."""


def read(ctx):
    t = ctx["trace"]
    return float(sum(1 for iv in t["busy"].values() if iv)) if t else None
