"""From a profiler trace to numbers: the reduction the benchmark owns.

Two halves.  `device_events` needs jax and runs in the worker child,
the one process that holds the chip: it opens the `.xplane.pb` and
returns plain lists.  Everything else is arithmetic on those lists and
runs anywhere (the harness parent, the tests on a recorded trace):
busy union, idle share, gaps named by the job phase the host was in,
the top operations, and the roofline share from the volumes' bytes.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SYNC = "bench.sync:"      # TraceAnnotation name prefix; host ns follows


class UnknownDevice(KeyError):
    """A device_kind the peaks table does not list: an error, never a
    default."""


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json")
    return table[device_kind]


# -- xplane -> lists (worker child only) ------------------------------------

def op_name(event_name: str) -> str:
    """"%multiply_xor_fusion.6 = u32[1,838860]{...} fusion(...)" ->
    "multiply_xor_fusion.6": the trace names an operation by its whole
    HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def device_events(xplane_path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, duration_ns], ...]},
    "sync": [[host_ns, trace_ns], ...]}: the operations of each TPU
    plane's "XLA Ops" line, and this harness's own sync annotations
    from the host plane, which tie trace time to the host's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices: "dict[str, list]" = {}
    sync = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            devices[plane.name] = sorted(
                [op_name(str(e.name)), int(e.start_ns), int(e.duration_ns)]
                for ln in ops for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if str(e.name).startswith(SYNC):
                        sync.append([int(str(e.name)[len(SYNC):]),
                                     int(e.start_ns)])
    return {"devices": devices, "sync": sync}


# -- arithmetic ---------------------------------------------------------------

def union(intervals: "list[tuple[float, float]]"
          ) -> "list[tuple[float, float]]":
    """Merged [start, end) intervals, sorted."""
    out: "list[list[float]]" = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def host_offset_ns(sync: "list[list[int]]") -> "float | None":
    """host_ns - trace_ns, the median over the sync annotations."""
    if not sync:
        return None
    d = sorted(h - t for h, t in sync)
    return float(d[len(d) // 2])


def busy_by_device(events: dict, lo_s: float, hi_s: float
                   ) -> "dict[str, list[tuple[float, float]]]":
    """Each device's busy union on the host's clock in seconds, clipped
    to the window.  Without a sync annotation trace time cannot be tied
    to the window and nothing is returned."""
    off = host_offset_ns(events.get("sync", []))
    if off is None:
        return {}
    out = {}
    for plane, evs in events.get("devices", {}).items():
        iv = [((s + off) / 1e9, (s + d + off) / 1e9) for _n, s, d in evs]
        out[plane] = clip(union(iv), lo_s, hi_s)
    return out


def busy_seconds(busy: dict) -> "float | None":
    """Seconds an operation ran, averaged over the chips used."""
    if not busy:
        return None
    return sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy)


def idle_share(busy_s: "float | None", window_s: float
               ) -> "float | None":
    if busy_s is None or window_s <= 0:
        return None
    return 1.0 - busy_s / window_s


def top_ops(events: dict, lo_s: float, hi_s: float, n: int = 10
            ) -> "list[list]":
    """[[name, seconds], ...]: device operations by total time in the
    window, largest first."""
    off = host_offset_ns(events.get("sync", []))
    if off is None:
        return []
    total: "dict[str, float]" = {}
    for evs in events.get("devices", {}).values():
        for name, s, d in evs:
            a, b = (s + off) / 1e9, (s + d + off) / 1e9
            if min(b, hi_s) > max(a, lo_s):
                total[name] = total.get(name, 0.0) + \
                    min(b, hi_s) - max(a, lo_s)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def gaps_by_phase(busy: dict, phases: "list[tuple[str, float, float]]",
                  lo_s: float, hi_s: float, n: int = 10) -> "list[list]":
    """[[phase, idle seconds], ...]: the window's idle time (no chip
    busy) split by what the host was doing.  `phases` are (name,
    start, end) on the host's clock; idle time under no phase is
    "between_jobs"."""
    if not busy:
        return []
    all_busy = union([iv for ivs in busy.values() for iv in ivs])
    idle, at = [], lo_s
    for s, e in all_busy:
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi_s > at:
        idle.append((at, hi_s))
    total: "dict[str, float]" = {}
    covered = 0.0
    for name, ps, pe in phases:
        got = sum(e - s for s, e in clip(idle, ps, pe))
        if got > 0:
            total[name] = total.get(name, 0.0) + got
            covered += got
    rest = sum(e - s for s, e in idle) - covered
    if rest > 1e-9:
        total["between_jobs"] = rest
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def encode_min_bytes(volume_bytes: float, k: int, r: int) -> float:
    """The least bytes a GF(2^8) RS(k, r) encode of `volume_bytes` of
    `.dat` moves through device memory: every data byte read once, every
    parity byte written once."""
    return volume_bytes * (k + r) / k


def roofline_share(min_bytes: float, busy_s: "float | None",
                   device_kind: str, chips: int = 1) -> "float | None":
    """Percent: least time at the table's HBM bytes/s, on the cell's
    `chips` together, over the time a device was busy (`busy_seconds`:
    the mean over the chips).  Bandwidth-bound: the encode does ~(k+r)/k
    bytes of traffic for a handful of byte operations each.  Chips that
    split the bytes perfectly read 100, never `chips` times that,
    wherever the program placed the work."""
    peak = peaks_for(device_kind)["hbm_bytes_per_s"] * chips
    if not busy_s or min_bytes <= 0:
        return None
    return 100.0 * (min_bytes / peak) / busy_s
