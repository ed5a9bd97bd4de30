"""Volume GB/s the background jobs finished: all their bytes over first
submit to last finish."""

from benchmark.run import job_rate_GBps


def read(ctx):
    return job_rate_GBps(ctx["jobs"])
