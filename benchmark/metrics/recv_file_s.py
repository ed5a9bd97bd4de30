"""Seconds a push's receiver spends on the file's half of its copy: the
`busySeconds` of the `receive.file` span under each volume role's `POST
/admin/receive_file` span found in the jobs' traces (the pushed shard
written into the role's tmpfs), mean over those spans
(benchmark/copy_halves.py)."""

from benchmark import copy_halves


def read(ctx):
    return copy_halves.mean_busy(ctx, "receive", "file")
