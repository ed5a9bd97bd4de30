"""The two halves of the job path's socket-to-file copies, read from the
window's job traces (benchmark/job_trace.py): `pull.socket` and
`pull.file` under each `ec.pull` (the worker's copy of a volume's `.dat`
and `.idx` out of the source's socket into its tmpfs), `receive.socket`
and `receive.file` under each volume role's `POST /admin/receive_file`
(the receiving end of a push), each span {calls, busySeconds,
cpuSeconds, bytes}.  A program that records no such span (one older
than them) leaves its readers nothing to read: they return None."""

from __future__ import annotations

from benchmark import job_trace

# a half's stage: (the span it hangs under, that span's role)
PARENT = {"pull": ("ec.pull", None),
          "receive": ("POST /admin/receive_file", "volume")}
HALVES = (("pull", "socket"), ("pull", "file"),
          ("receive", "socket"), ("receive", "file"))


def parents(spans: "list[dict]", stage: str) -> "list[dict]":
    name, role = PARENT[stage]
    return [s for s in spans
            if s["name"] == name and role in (None, s["role"])]


def by_job(ctx: dict, stage: str, half: str) -> "list[list[dict]]":
    """Each job's `<stage>.<half>` spans that hang under a span of
    their stage, in the jobs' order."""
    out = []
    for spans in job_trace.job_traces(ctx):
        ids = {s["spanId"] for s in parents(spans, stage)}
        out.append([s for s in spans if s["name"] == f"{stage}.{half}"
                    and s.get("parentId") in ids])
    return out


def mean_busy(ctx: dict, stage: str, half: str) -> "float | None":
    """The half's `busySeconds`, mean over the spans of its stage that
    have one; nothing where none has, or where one lacks its CPU
    seconds (job_trace.attr_sum says so)."""
    spans = [s for job in by_job(ctx, stage, half) for s in job]
    if not spans or job_trace.attr_sum(spans, "cpuSeconds") is None:
        return None
    busy = job_trace.attr_sum(spans, "busySeconds")
    return None if busy is None else busy / len(spans)


def _part(spans: "list[dict]") -> str:
    """Mean busy seconds a span (CPU seconds over busy seconds)."""
    if not spans:
        return "-"
    busy = sum(s["attrs"]["busySeconds"] for s in spans)
    cpu = sum(s["attrs"].get("cpuSeconds", 0.0) for s in spans)
    return f"{busy / len(spans):.4f} s ({cpu / busy if busy else 0:.3f})"


def say(ctx: dict) -> None:
    """One line: each half's mean busy seconds and its CPU over busy,
    the window's first job against the rest; the halves' sum over the
    span they hang under; and how many receiver spans were found of the
    pushes sent."""
    traces = job_trace.job_traces(ctx)
    pushes = [s for spans in traces for s in spans
              if s["name"] == "ec.push"]
    push_ids = {s["spanId"] for s in pushes}
    found = [s for spans in traces for s in parents(spans, "receive")
             if s.get("parentId") in push_ids]
    parts, share = [], []
    for stage, half in HALVES:
        jobs = by_job(ctx, stage, half)
        if not any(jobs):
            continue
        parts.append(f"{stage}.{half} {_part(jobs[0])} | "
                     f"{_part([s for job in jobs[1:] for s in job])}")
    for stage in PARENT:
        halves = [s for half in ("socket", "file")
                  for job in by_job(ctx, stage, half) for s in job]
        ids = {h["parentId"] for h in halves}
        took = sum(s["durationMs"] for spans in traces
                   for s in parents(spans, stage) if s["spanId"] in ids)
        if took > 0:
            busy = sum(h["attrs"]["busySeconds"] for h in halves)
            share.append(f"{stage} {busy / (took / 1e3):.3f}")
    print("  copy halves, busy seconds a span (CPU over busy), the first "
          "job | the rest: " + ("; ".join(parts) or
                                "none in the jobs' traces")
          + (f"; the halves over the span they hang under: "
             f"{', '.join(share)}" if share else "")
          + f"; receiver spans found: {len(found)} of {len(pushes)} "
          "pushes", flush=True)
