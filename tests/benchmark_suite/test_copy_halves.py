"""`pull_socket_s`, `pull_file_s`, `recv_socket_s`, `recv_file_s`: the
two halves of the job path's socket-to-file copies, read from the stage
spans `pull.socket` / `pull.file` under each `ec.pull` and
`receive.socket` / `receive.file` under each volume role's `POST
/admin/receive_file` (benchmark/copy_halves.py)."""

import copy
import json
import os

import pytest

from bench_tree import spec_tree  # noqa: F401 — a fixture
from benchmark import job_trace, run

BENCH = os.path.join(run.REPO, "benchmark")
CELL = "ec10_4_vol1g.encode"
READERS = {   # name: (stage, half, layer)
    "pull_socket_s": ("pull", "socket", "maintenance plane"),
    "pull_file_s": ("pull", "file", "maintenance plane"),
    "recv_socket_s": ("receive", "socket", "serving planes"),
    "recv_file_s": ("receive", "file", "serving planes"),
}


def reader(name):
    return run.metric_reader(BENCH, name)


def recorded():
    """An older recording of the encode cell on the v5e: the program
    before the copy halves, five jobs of 146 spans each."""
    with open(os.path.join(BENCH, "testdata", "job_traces_v5e.json")) as f:
        rec = json.load(f)
    return rec["ctx"], rec["traces"]


def with_halves(traces, busy=None, cpu_share=0.9):
    """The recording as the program records it now: a socket and a file
    half under every `ec.pull` and every receiver span, the two halves
    `busy`(parent's seconds) of it, the socket half 0.4 of that."""
    out = copy.deepcopy(traces)
    n = 0
    for spans in out:
        for s in list(spans):
            stage = {"ec.pull": "pull",
                     "POST /admin/receive_file": "receive"}.get(s["name"])
            if stage is None:
                continue
            took = s["durationMs"] / 1e3
            both = busy(s) if busy else 0.9 * took
            for half, part in (("socket", 0.4), ("file", 0.6)):
                n += 1
                spans.append({
                    "traceId": s["traceId"], "spanId": f"h{n}",
                    "parentId": s["spanId"], "role": s["role"],
                    "name": f"{stage}.{half}", "start": s["start"],
                    "durationMs": s["durationMs"],
                    "attrs": {"calls": 26, "busySeconds": both * part,
                              "cpuSeconds": both * part * cpu_share,
                              "bytes": s["attrs"]["bytes"]}})
    return out


def expected(traces, stage, half):
    name = {"pull": "ec.pull", "receive": "POST /admin/receive_file"}[stage]
    part = {"socket": 0.4, "file": 0.6}[half]
    took = [0.9 * s["durationMs"] / 1e3 * part for spans in traces
            for s in spans if s["name"] == name]
    return sum(took) / len(took)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_reads_the_mean_busy_seconds_of_its_half(name):
    ctx, traces = recorded()
    stage, half, _layer = READERS[name]
    job_trace.preload(ctx, with_halves(traces))
    got = reader(name)(ctx)
    assert isinstance(got, float)
    assert got == pytest.approx(expected(traces, stage, half))


def on_the_chip():
    """The first four jobs of a traced window of the encode cell on the
    v5e, with the copy halves the program records."""
    with open(os.path.join(BENCH, "testdata", "copy_halves_v5e.json")) as f:
        rec = json.load(f)
    return rec["ctx"], rec["traces"]


def test_the_readers_on_the_chips_recording(capfd):
    ctx, traces = on_the_chip()
    job_trace.preload(ctx, traces)
    got = {name: reader(name)(ctx) for name in sorted(READERS)}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    spans = [s for t in traces for s in t]

    def mean_s(name):
        d = [s["durationMs"] / 1e3 for s in spans if s["name"] == name]
        return sum(d) / len(d)

    # each stage's halves fill 0.8-1.0 of the span they hang under
    pull = (got["pull_socket_s"] + got["pull_file_s"]) / mean_s("ec.pull")
    recv = (got["recv_socket_s"] + got["recv_file_s"]) / \
        mean_s("POST /admin/receive_file")
    assert 0.8 <= pull <= 1.0 and 0.8 <= recv <= 1.0, (pull, recv)
    # the window's first job pulls slower, in the file's half
    busy = {half: [sum(s["attrs"]["busySeconds"] for s in t
                       if s["name"] == f"pull.{half}") for t in traces]
            for half in ("socket", "file")}
    assert busy["file"][0] > 1.2 * max(busy["file"][1:])
    assert max(busy["socket"]) < 1.05 * min(busy["socket"])
    out = capfd.readouterr().out
    assert "receiver spans found: 80 of 80 pushes" in out


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_parents_program_reads_nothing_not_nought(name):
    ctx, traces = recorded()
    job_trace.preload(ctx, traces)
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_where_no_span_was_found(name):
    ctx = {"jobs": [{"id": "j0", "ok": True}]}
    job_trace.preload(ctx, [[]])       # the ring had turned over
    assert reader(name)(ctx) is None
    # a half whose parent span is not in the trace is not counted
    job_trace.preload(ctx, [[{
        "spanId": "x", "parentId": "gone", "role": "volume",
        "name": f"{READERS[name][0]}.{READERS[name][1]}", "start": 0.0,
        "durationMs": 1.0, "attrs": {"calls": 1, "busySeconds": 0.5,
                                     "cpuSeconds": 0.5, "bytes": 1}}]])
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_half_without_its_cpu_seconds_is_said_and_reads_nothing(
        name, capfd):
    ctx, traces = recorded()
    got = with_halves(traces)
    stage, half, _layer = READERS[name]
    (victim,) = [s for s in got[1] if s["name"] == f"{stage}.{half}"][:1]
    del victim["attrs"]["cpuSeconds"]
    job_trace.preload(ctx, got)
    assert reader(name)(ctx) is None
    assert "cpuSeconds missing on 1 of" in capfd.readouterr().err


def test_the_line_says_first_job_against_the_rest_and_n_of_m(capfd):
    ctx, traces = recorded()
    # the first job's pull takes its halves twice as long
    first = traces[0][0]["traceId"]

    def busy(s):
        took = 0.9 * s["durationMs"] / 1e3
        return took * 2 if s["traceId"] == first and \
            s["name"] == "ec.pull" else took

    job_trace.preload(ctx, with_halves(traces, busy=busy))
    reader("pull_socket_s")(ctx)
    out = capfd.readouterr().out
    (line,) = [ln for ln in out.splitlines() if "copy halves" in ln]
    pushes = sum(s["name"] == "ec.push" for t in traces for s in t)
    found = sum(s["name"] == "POST /admin/receive_file"
                for t in traces for s in t)
    assert f"receiver spans found: {found} of {pushes} pushes" in line
    for stage, half, _layer in READERS.values():
        assert f"{stage}.{half} " in line
    assert "(0.900)" in line        # CPU over busy
    assert "the halves over the span they hang under: pull " in line
    assert "receive 0.900" in line


def test_the_line_on_the_parents_program_says_there_is_none(capfd):
    ctx, traces = recorded()
    job_trace.preload(ctx, traces)
    assert reader("pull_socket_s")(ctx) is None
    out = capfd.readouterr().out
    assert "copy halves" in out and "none in the jobs' traces" in out


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entries_are_the_encode_cells_and_come_last(spec_tree, name):
    """Each entry as PR 40 appended it, and the four together in its
    order after every entry that was there before them; entries a later
    PR appends after them (the plus-one tree's) break nothing."""
    spec = spec_tree.spec
    (m,) = [e for e in spec["per_layer"] if e["name"] == name]
    assert m == {"name": name, "unit": "s", "better": "lower",
                 "source": "program_span", "layer": READERS[name][2],
                 "moves": "ec_GBps", "workloads": [CELL]}
    assert m in run.metrics_of(spec, "per_layer", CELL)
    names = [e["name"] for e in spec["per_layer"]]
    first = names.index("pull_socket_s")
    # right after PR 35's `push_dat_share`, the last entry before them
    assert names[first - 1:first + 4] == ["push_dat_share", *READERS]
    assert callable(run.metric_reader(spec_tree.bench, name))
