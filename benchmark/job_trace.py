"""The whole trace of each of the window's jobs, fetched the way an
operator fetches it: the job's request id from the admin's `GET
/maintenance/job?id=`, then `GET /debug/traces?request_id=` from the
admin (which holds the worker's spans: they ride the completion report)
and from every volume role, merged by span id as `trace.show` merges
them.  The metric readers that stand on the program's spans share this
file; the traces of a run are fetched once.

The context a reader is handed holds no address of any role, and the
files the benchmark already has are not this PR's to edit, so the roles
are found as what they are: children of this process, by their command
lines under /proc (`python -m seaweedfs_tpu admin -port N`, `... volume
-port N`).  A stopgap, and PERF.md 7 says so: the next benchmark issue
puts the roles' addresses, or the traces, into the context.

A role that cannot be reached is an error with its reason.  A trace
that holds no span of the wanted name is not: the program at an older
commit has no such span, and the reader then returns nothing.
"""

from __future__ import annotations

import os
import sys

ROLES = ("admin", "volume")


class TraceUnreachable(RuntimeError):
    """No admin among this process's children, a job the admin does not
    know, or a role that does not answer."""


def child_roles(parent: "int | None" = None) -> "dict[str, list[str]]":
    """{"admin": ["127.0.0.1:port"], "volume": [...]}: the roles this
    process started, read from /proc."""
    parent = os.getpid() if parent is None else parent
    found: "dict[str, list[str]]" = {r: [] for r in ROLES}
    for ent in os.listdir("/proc"):
        if not ent.isdigit():
            continue
        try:
            with open(f"/proc/{ent}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid != parent:
                continue
            with open(f"/proc/{ent}/cmdline", "rb") as f:
                argv = f.read().decode("utf-8", "replace").split("\0")
        except (OSError, ValueError, IndexError):
            continue
        addr = role_address(argv)
        if addr:
            found[addr[0]].append(addr[1])
    return found


def role_address(argv: "list[str]") -> "tuple[str, str] | None":
    """("volume", "127.0.0.1:8080") from `python -m seaweedfs_tpu
    volume -port 8080 ...`; None for any other command line."""
    try:
        at = argv.index("seaweedfs_tpu")
        role = argv[at + 1]
        port = int(argv[argv.index("-port", at) + 1])
    except (ValueError, IndexError):
        return None
    if at == 0 or argv[at - 1] != "-m" or role not in ROLES:
        return None
    return role, f"127.0.0.1:{port}"


def merge(span_lists: "list[list[dict]]") -> "list[dict]":
    """One list, each span id once (the first seen), by start time."""
    merged: "dict[str, dict]" = {}
    for spans in span_lists:
        for s in spans:
            merged.setdefault(s["spanId"], s)
    return sorted(merged.values(), key=lambda s: s["start"])


def fetch_trace(job_id: str, roles: "dict[str, list[str]]"
                ) -> "list[dict]":
    from seaweedfs_tpu.server.httpd import http_json
    if not roles["admin"]:
        raise TraceUnreachable(
            "no `seaweedfs_tpu admin -port N` among this process's "
            "children: the job's trace cannot be asked for")
    rid, errors = "", []
    for admin in roles["admin"]:
        try:
            job = http_json("GET", f"{admin}/maintenance/job?id={job_id}",
                            timeout=10)
        except OSError as e:
            errors.append(f"{admin}: {e!r}")
            continue
        if job.get("jobId") == job_id:
            rid = job.get("requestId") or f"job-{job_id}"
            break
        errors.append(f"{admin}: {job}")
    if not rid:
        raise TraceUnreachable(f"no admin knows job {job_id}: {errors}")
    lists = []
    for node in [admin] + roles["volume"]:
        try:
            got = http_json("GET", f"{node}/debug/traces?request_id={rid}",
                            timeout=10)
        except OSError as e:
            raise TraceUnreachable(
                f"{node}/debug/traces did not answer: {e!r}") from e
        lists.append([dict(s, node=node) for s in got.get("spans", [])])
    return merge(lists)


_cache: "dict[tuple, list[list[dict]]]" = {}


def _ids(ctx: dict) -> tuple:
    return tuple(j["id"] for j in ctx["jobs"] if j["ok"])


def preload(ctx: dict, traces: "list[list[dict]]") -> None:
    """Recorded traces in place of fetched ones (the tests' way in)."""
    _cache.clear()
    _cache[_ids(ctx)] = traces


def job_traces(ctx: dict) -> "list[list[dict]]":
    """The merged trace of each of the window's jobs that ended well,
    in the jobs' order; fetched once a run."""
    ids = _ids(ctx)
    if ids not in _cache:
        roles = child_roles()
        _cache.clear()
        _cache[ids] = [fetch_trace(job_id, roles) for job_id in ids]
        phases = {j["id"]: j.get("phases", {}) for j in ctx["jobs"]}
        for job_id, spans in zip(ids, _cache[ids]):
            names: "dict[str, int]" = {}
            for s in spans:
                names[s["name"]] = names.get(s["name"], 0) + 1
            # the program's own span beside the phase the harness cuts
            # out of the progress messages: they time the same thing
            dist = [s["durationMs"] / 1e3 for s in spans
                    if s["name"] == "ec.distribute"]
            mark = phases[job_id].get("distribute")
            both = f"ec.distribute {sum(dist):.3f}s ({len(dist)} spans) " \
                f"against {mark[1] - mark[0]:.3f}s by progress marks; " \
                if dist and mark else ""
            print(f"  trace of job {job_id}: {both}{len(spans)} spans"
                  + (f" {dict(sorted(names.items()))}"
                     if job_id == ids[0] else ""), flush=True)
    return _cache[ids]


def named(ctx: dict, name: str, role: "str | None" = None
          ) -> "list[dict]":
    """Every span of that name (and role) in the window's jobs' traces."""
    return [s for spans in job_traces(ctx) for s in spans
            if s["name"] == name and (role is None or s["role"] == role)]


def seconds(spans: "list[dict]") -> float:
    return sum(s["durationMs"] for s in spans) / 1e3


def attr_sum(spans: "list[dict]", key: str) -> "float | None":
    """The sum of an attribute over the spans; nothing where a span
    lacks it (a share of part of the spans would be a made-up number)."""
    vals = [(s.get("attrs") or {}).get(key) for s in spans]
    if not vals or any(v is None for v in vals):
        if vals:
            sys.stderr.write(f"job_trace: {key} missing on "
                             f"{sum(v is None for v in vals)} of "
                             f"{len(vals)} {spans[0]['name']} spans\n")
        return None
    return float(sum(vals))
