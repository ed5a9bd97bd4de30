"""Seconds a pull spends on the file's half of its copy: the
`busySeconds` of the `pull.file` span under each of the window's
`ec.pull` spans (the pulled `.dat` and `.idx` written into the worker's
tmpfs), mean over the pulls (benchmark/copy_halves.py)."""

from benchmark import copy_halves


def read(ctx):
    return copy_halves.mean_busy(ctx, "pull", "file")
