"""What the roles of a run say on `/metrics`, for the readers that
stand on the program's own counters and are handed no address: the
master and the volume servers are found as `benchmark/job_trace.py`
finds the admin, among this process's children by their command lines
under /proc (the same stopgap, PERF.md 7), and each is scraped once a
run.

The families are cumulative since the role started, so a reader of
this file counts the whole run: the set-up's reads and jobs, the
window, and what the comparison read back after it.  A family the
program at an older commit does not have is simply not there:
`by_label` and `histogram` then say None, and the reader returns nothing.
"""

from __future__ import annotations

import os

from benchmark import job_trace

PREFIX = "seaweedfs_tpu_"


def child_argvs(parent: "int | None" = None) -> "list[list[str]]":
    """The command line of every child of this process."""
    parent = os.getpid() if parent is None else parent
    out = []
    for ent in os.listdir("/proc"):
        if not ent.isdigit():
            continue
        try:
            with open(f"/proc/{ent}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid != parent:
                continue
            with open(f"/proc/{ent}/cmdline", "rb") as f:
                out.append(f.read().decode("utf-8", "replace").split("\0"))
        except (OSError, ValueError, IndexError):
            continue
    return out


def master_address(argv: "list[str]") -> "str | None":
    """"127.0.0.1:9333" from `python -m seaweedfs_tpu master -port
    9333 ...`; None for any other command line."""
    try:
        at = argv.index("seaweedfs_tpu")
        if at == 0 or argv[at - 1] != "-m" or argv[at + 1] != "master":
            return None
        return f"127.0.0.1:{int(argv[argv.index('-port', at) + 1])}"
    except (ValueError, IndexError):
        return None


def addresses(argvs: "list[list[str]]") -> "dict[str, list[str]]":
    found: "dict[str, list[str]]" = {"master": [], "volume": []}
    for argv in argvs:
        m = master_address(argv)
        if m:
            found["master"].append(m)
        role = job_trace.role_address(argv)
        if role and role[0] == "volume":
            found["volume"].append(role[1])
    return found


_cache: "dict[int, dict[str, list[dict]]]" = {}


def preload(ctx: dict, scraped: "dict[str, list[dict]]") -> None:
    """Recorded scrapes in place of fetched ones (the tests' way in):
    {"master": [parsed /metrics, ...], "volume": [...]}, each parsed
    as `profiling.parse_prom_text` gives it."""
    _cache.clear()
    _cache[id(ctx)] = scraped


def scraped(ctx: dict) -> "dict[str, list[dict]]":
    """{"master": [...], "volume": [...]}: each role's /metrics, parsed
    to {family: [(labels, value), ...]}; fetched once a run.  A role
    that does not answer is an error: a sum over the rest would be a
    made-up number."""
    if id(ctx) not in _cache:
        from seaweedfs_tpu.profiling import parse_prom_text
        from seaweedfs_tpu.server.httpd import http_bytes
        roles = addresses(child_argvs())
        want = ctx["cfg"]["volume_servers"]
        if len(roles["master"]) != 1 or len(roles["volume"]) != want:
            raise job_trace.TraceUnreachable(
                f"looked for 1 master and {want} volume servers among "
                f"this process's children, found {roles}")
        got: "dict[str, list[dict]]" = {}
        for role, urls in roles.items():
            got[role] = []
            for url in urls:
                try:
                    status, body, _ = http_bytes(
                        "GET", f"{url}/metrics", timeout=10)
                except OSError as e:
                    raise job_trace.TraceUnreachable(
                        f"{url}/metrics did not answer: {e!r}") from e
                if status != 200:
                    raise job_trace.TraceUnreachable(
                        f"{url}/metrics answered {status}")
                got[role].append(parse_prom_text(
                    body.decode("utf-8", "replace")))
        _cache.clear()
        _cache[id(ctx)] = got
    return _cache[id(ctx)]


def ran(ctx: dict) -> bool:
    """Whether the window held anything: a context with no job and no
    reader is no run's, and there is no role to ask."""
    return bool(ctx.get("jobs") or ctx.get("reads"))


def by_label(ctx: dict, role: str, family: str, label: str
             ) -> "dict[str, float] | None":
    """{label value: sum over the role's processes} of one counter
    family; None where no process of the role has the family."""
    if not ran(ctx):
        return None
    out: "dict[str, float]" = {}
    seen = False
    for parsed in scraped(ctx)[role]:
        for labels, value in parsed.get(PREFIX + family, []):
            seen = True
            key = labels.get(label, "")
            out[key] = out.get(key, 0.0) + value
    return out if seen else None


def histogram(ctx: dict, role: str, family: str) -> "dict | None":
    """One histogram merged over the role's processes:
    {"count", "sum", "slowest_le": the upper bound of the highest
    bucket that holds a sample ("+Inf" above the last)}."""
    from seaweedfs_tpu.profiling import prom_histogram
    if not ran(ctx):
        return None
    count = total = 0.0
    slowest = None
    for parsed in scraped(ctx)[role]:
        h = prom_histogram(parsed, PREFIX + family)
        if not h or not h["count"]:
            continue
        count += h["count"]
        total += h["sum"]
        les = list(h["buckets"]) + [float("inf")]
        top = max(le for le, n in zip(les, h["counts"]) if n)
        slowest = top if slowest is None else max(slowest, top)
    if not count:
        return None
    return {"count": count, "sum": total,
            "slowest_le": "+Inf" if slowest == float("inf") else slowest}
