"""Seconds a job spent pulling its volumes to the worker: the sum of
its `ec.pull` spans (a job of several volumes pulls them one after
another), mean over the window's jobs."""

from benchmark import job_trace


def read(ctx):
    per_job = [sum(s["durationMs"] for s in spans
                   if s["name"] == "ec.pull") / 1e3
               for spans in job_trace.job_traces(ctx)]
    per_job = [t for t in per_job if t > 0]
    return sum(per_job) / len(per_job) if per_job else None
