"""Seconds a pull spends on the socket's half of its copy: the
`busySeconds` of the `pull.socket` span under each of the window's
`ec.pull` spans (the worker reading the source's `.dat` and `.idx` out
of its socket), mean over the pulls.  Prints the four halves of the
job path's copies, the first job against the rest, and the receiver
spans found of the pushes sent (benchmark/copy_halves.py)."""

from benchmark import copy_halves


def read(ctx):
    copy_halves.say(ctx)
    return copy_halves.mean_busy(ctx, "pull", "socket")
