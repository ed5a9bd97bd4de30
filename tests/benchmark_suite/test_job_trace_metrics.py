"""The per-layer metrics that stand on the program's spans and on the
staging ledger's new keys (PR 25): each reader on a context and job
traces recorded on the v5e, `benchmark/job_trace.py`'s merge and its
look for the roles among this process's children against the rehearsal
cluster, and the entries `BENCHMARK.json` gained."""

import json
import os

import pytest

from bench_tree import spec_tree  # noqa: F401 — a fixture
from benchmark import job_trace, run, trace_reduce

CELL = "ec10_4_vol1g.encode"
NEW = {
    "job_distribute_s": ("s", "lower", "program_span", "maintenance plane"),
    "push_GBps": ("GB/s", "higher", "program_span", "maintenance plane"),
    "push_sender_cpu_share": ("share", "lower", "program_span",
                              "maintenance plane"),
    "push_receiver_cpu_share": ("share", "lower", "program_span",
                                "serving planes"),
    "enc_idle_h2d_share": ("share", "lower", "device_trace",
                           "EC file pipeline"),
    "staging_pack_share": ("share", "lower", "program_counter", "staging"),
    "staging_pad_share": ("share", "lower", "program_counter", "staging"),
    "staging_slot_wait_s": ("s", "lower", "program_counter", "staging"),
    "staging_ready_wait_s": ("s", "lower", "program_counter", "staging"),
    "push_phase_GBps": ("GB/s", "higher", "program_span",
                        "maintenance plane"),
}
BENCH = os.path.join(run.REPO, "benchmark")


def reader(name):
    return run.metric_reader(BENCH, name)


# -- the entries ----------------------------------------------------------

def test_the_new_entries_stand_last_each_with_reader_cells_and_moves(
        spec_tree):
    spec = spec_tree.spec
    # found by name and in their order: every later PR appends entries
    tail = [m for m in spec["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in tail] == list(NEW)
    e2e = {m["name"] for m in run.metrics_of(spec, "end_to_end", CELL)}
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in NEW} | \
        {"serving planes"}          # PERF.md 3's name for the volume roles
    for m in tail:
        unit, better, source, layer = NEW[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (unit, better, source, layer)
        assert m["workloads"] == [CELL] and m["moves"] == "ec_GBps"
        assert m["moves"] in e2e and m["layer"] in layers
        assert os.path.exists(os.path.join(spec_tree.bench, "metrics",
                                           m["name"] + ".py"))
        assert callable(run.metric_reader(spec_tree.bench, m["name"]))


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_returns_nothing_where_the_program_has_nothing(name):
    """The parent commit has no such span and no such key: the line
    then leaves the metric out, and nothing raises."""
    ctx = {"jobs": [{"id": "j1", "ok": True, "bytes": 10, "phases": {}}],
           "staging": {"h2d_bytes": 100, "h2d_seconds": 1.0},
           "trace": {"busy": {"/device:TPU:0": [(1.0, 2.0)]},
                     "busy_s": 1.0, "window_s": 10.0}}
    old = [{"spanId": "a", "name": "job:erasure_coding", "role": "worker",
            "start": 0.0, "durationMs": 9000.0},
           {"spanId": "b", "name": "ec.encode", "role": "worker",
            "start": 1.0, "durationMs": 2000.0}]
    job_trace.preload(ctx, [old])
    assert reader(name)(ctx) is None
    job_trace.preload(ctx, [[]])       # the ring had turned over
    assert reader(name)(ctx) is None


# -- the readers on what a v5e run recorded ---------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "testdata",
                           "job_traces_v5e.json")) as f:
        rec = json.load(f)
    ctx = rec["ctx"]
    ctx["trace"]["busy"] = {k: [tuple(iv) for iv in v]
                            for k, v in ctx["trace"]["busy"].items()}
    return ctx, rec["traces"]


def spans_of(traces, name, role=None):
    return [s for t in traces for s in t
            if s["name"] == name and role in (None, s["role"])]


def test_recorded_traces_are_whole(recorded):
    ctx, traces = recorded
    assert len(traces) == sum(j["ok"] for j in ctx["jobs"]) >= 4
    for job, spans in zip(ctx["jobs"], traces):
        ids = {s["spanId"] for s in spans}
        assert len(ids) == len(spans)
        root = [s for s in spans if s["name"] == "job:erasure_coding"]
        assert len(root) == 1
        assert all(s["traceId"] == root[0]["traceId"] for s in spans)
        pushes = [s for s in spans if s["name"] == "ec.push"]
        assert len(pushes) == 14 + 2 * 3
        got = [s for s in spans if s["name"] == "POST /admin/receive_file"]
        assert {s["parentId"] for s in got} == {s["spanId"] for s in pushes}
        # 14 shards of ceil(rows) MiB blocks, .ecx and .vif three times
        shard = {s["attrs"]["bytes"] for s in pushes
                 if s["attrs"]["ext"].startswith(".ec0")}
        assert len(shard) == 1 and shard.pop() * 10 >= job["bytes"]
        dist = [s for s in spans if s["name"] == "ec.distribute"][0]
        assert dist["attrs"]["bytes"] == \
            sum(s["attrs"]["bytes"] for s in pushes)
        # the program's span and the harness's phase time the same thing
        lo, hi = job["phases"]["distribute"]
        assert abs(dist["durationMs"] / 1e3 - (hi - lo)) < 0.2


def test_span_readers_on_the_recording(recorded):
    ctx, traces = recorded
    job_trace.preload(ctx, traces)
    dist = spans_of(traces, "ec.distribute")
    assert reader("job_distribute_s")(ctx) == pytest.approx(
        sum(s["durationMs"] for s in dist) / 1e3 / len(dist))
    assert 7.0 < reader("job_distribute_s")(ctx) < 11.0
    push = spans_of(traces, "ec.push")
    took = sum(s["durationMs"] for s in push) / 1e3
    assert reader("push_GBps")(ctx) == pytest.approx(
        sum(s["attrs"]["bytes"] for s in push) / took / 1e9)
    assert 0.1 < reader("push_GBps")(ctx) < 0.3
    assert reader("push_sender_cpu_share")(ctx) == pytest.approx(
        sum(s["attrs"]["cpuSeconds"] for s in push) / took)
    recv = spans_of(traces, "POST /admin/receive_file", "volume")
    assert reader("push_receiver_cpu_share")(ctx) == pytest.approx(
        sum(s["attrs"]["cpuSeconds"] for s in recv) / took)
    assert 0.5 < reader("push_sender_cpu_share")(ctx) <= 1.02
    assert 0.2 < reader("push_receiver_cpu_share")(ctx) <= 1.02
    # PR 25's program has no `pushSeconds` on its distribute span
    assert reader("push_phase_GBps")(ctx) is None


def without_receivers(traces, lost):
    """The traces as a volume role's ring leaves them once it has
    turned over: the receiver spans of the first `lost` jobs gone."""
    return [[s for s in t if i >= lost
             or s["name"] != "POST /admin/receive_file"]
            for i, t in enumerate(traces)]


@pytest.mark.parametrize("lost", [0, 1, 3])
def test_the_receivers_share_counts_only_the_pushes_whose_span_was_found(
        recorded, lost):
    """Divided by the seconds of every push, a rolled-over ring read
    as a receiver that waits (0.61 printed where 0.94 was true, PR 32)."""
    ctx, traces = recorded
    job_trace.preload(ctx, traces)
    whole = reader("push_receiver_cpu_share")(ctx)
    job_trace.preload(ctx, without_receivers(traces, lost))
    got = reader("push_receiver_cpu_share")(ctx)
    kept = traces[lost:]
    assert got == pytest.approx(
        sum(s["attrs"]["cpuSeconds"] for s in spans_of(
            kept, "POST /admin/receive_file", "volume"))
        / (sum(s["durationMs"] for s in spans_of(kept, "ec.push")) / 1e3))
    assert got == pytest.approx(whole, rel=0.25)
    # the sender's share and the stream's rate still count every push
    assert reader("push_GBps")(ctx) == pytest.approx(
        sum(s["attrs"]["bytes"] for s in spans_of(traces, "ec.push"))
        / (sum(s["durationMs"] for s in spans_of(traces, "ec.push")) / 1e3)
        / 1e9)


def test_no_receiver_span_found_is_nothing_not_nought(recorded):
    ctx, traces = recorded
    job_trace.preload(ctx, without_receivers(traces, len(traces)))
    assert reader("push_receiver_cpu_share")(ctx) is None
    assert reader("push_sender_cpu_share")(ctx) is not None


@pytest.mark.parametrize("push_seconds,want", [
    ([0.5, 0.5], 2.0), ([0.25, 1.0], 1.6), ([None, 0.5], 2.0),
    ([None, None], None)])
def test_the_phases_rate_is_the_bytes_over_push_seconds(push_seconds, want):
    """`ec.distribute` {bytes, pushSeconds}: first push's start to the
    last one's end; a span without the attribute (an older program's)
    is left out, bytes and all."""
    ctx = {"jobs": [{"id": f"j{i}", "ok": True}
                    for i in range(len(push_seconds))]}
    job_trace.preload(ctx, [[{
        "spanId": f"d{i}", "name": "ec.distribute", "role": "worker",
        "start": float(i), "durationMs": 900.0, "attrs": dict(
            {"bytes": 10**9}, **({} if p is None else {"pushSeconds": p}))}]
        for i, p in enumerate(push_seconds)])
    got = reader("push_phase_GBps")(ctx)
    assert got == (None if want is None else pytest.approx(want))


def test_enc_idle_h2d_share_on_the_recording(recorded):
    ctx, traces = recorded
    job_trace.preload(ctx, traces)
    got = reader("enc_idle_h2d_share")(ctx)
    # by another road: a 1 ms grid over each encode span
    busy = trace_reduce.union([iv for v in ctx["trace"]["busy"].values()
                               for iv in v])
    h2d = [(s["start"], s["start"] + s["durationMs"] / 1e3)
           for s in spans_of(traces, "stage.h2d")]

    def inside(t, ivs):
        return any(a <= t < b for a, b in ivs)
    idle = under = 0
    for s in spans_of(traces, "ec.encode"):
        lo, hi = s["start"], s["start"] + s["durationMs"] / 1e3
        near_busy = [iv for iv in busy if iv[1] >= lo and iv[0] <= hi]
        near_h2d = [iv for iv in h2d if iv[1] >= lo and iv[0] <= hi]
        t = lo
        while t < hi:
            if not inside(t, near_busy):
                idle += 1
                under += inside(t, near_h2d)
            t += 1e-3
    assert got == pytest.approx(under / idle, abs=0.01)
    assert 0.3 < got < 0.9
    assert reader("enc_idle_h2d_share")(dict(ctx, trace=None)) is None


def test_counter_readers_on_the_recording(recorded):
    ctx, _traces = recorded
    s, done = ctx["staging"], sum(j["ok"] for j in ctx["jobs"])
    assert reader("staging_pack_share")(ctx) == pytest.approx(
        s["pack_seconds"] / s["h2d_seconds"])
    assert 0 < reader("staging_pack_share")(ctx) <= 1
    # 102 rows of 10 MiB are sent as 128: every fifth byte is padding
    assert reader("staging_pad_share")(ctx) == pytest.approx(
        1 - s["payload_bytes"] / s["h2d_bytes"])
    assert reader("staging_pad_share")(ctx) == pytest.approx(0.2062,
                                                             abs=0.001)
    # the ledger's payload is the jobs' .dat bytes (and the set-up's
    # none: the delta is the window's)
    launched = s["launches"] // 2
    assert s["payload_bytes"] == launched * ctx["jobs"][0]["bytes"]
    assert reader("staging_slot_wait_s")(ctx) == pytest.approx(
        s["slot_wait_seconds"] / done)
    assert reader("staging_ready_wait_s")(ctx) == pytest.approx(
        s["ready_wait_seconds"] / done)


# -- the helper -------------------------------------------------------------

def test_merge_keeps_each_span_once_in_time_order():
    a = [{"spanId": "1", "start": 2.0, "node": "admin"},
         {"spanId": "2", "start": 1.0, "node": "admin"}]
    b = [{"spanId": "2", "start": 1.0, "node": "vol"},
         {"spanId": "3", "start": 0.5, "node": "vol"}]
    got = job_trace.merge([a, b])
    assert [s["spanId"] for s in got] == ["3", "2", "1"]
    assert got[1]["node"] == "admin"          # the first seen is kept


@pytest.mark.parametrize("argv,want", [
    (["/usr/bin/python3", "-m", "seaweedfs_tpu", "admin", "-port", "23456",
      "-master", "127.0.0.1:9"], ("admin", "127.0.0.1:23456")),
    (["python", "-m", "seaweedfs_tpu", "volume", "-port", "8080", "-dir",
      "/x", "-mserver", "127.0.0.1:9"], ("volume", "127.0.0.1:8080")),
    (["python", "-m", "seaweedfs_tpu", "master", "-port", "9333"], None),
    (["python", "-m", "benchmark.worker_proc", "--admin", "a"], None),
    (["python", "-m", "seaweedfs_tpu", "volume"], None),
    (["seaweedfs_tpu", "volume", "-port", "1"], None),
    ([""], None),
])
def test_role_address_reads_a_command_line(argv, want):
    assert job_trace.role_address(argv) == want


def test_no_admin_among_the_children_is_an_error_with_its_reason():
    with pytest.raises(job_trace.TraceUnreachable, match="no .*admin"):
        job_trace.fetch_trace("j1", {"admin": [], "volume": []})
    with pytest.raises(job_trace.TraceUnreachable, match="no admin knows"):
        job_trace.fetch_trace("j1", {"admin": ["127.0.0.1:9"],
                                     "volume": []})


def test_traced_rehearsal_finds_its_roles_and_prints_the_new_metrics(
        capfd, monkeypatch):
    """`--rehearse --trace 1` of the encode cell: the roles are found
    under /proc as the cluster started them, the volume roles' spans
    (other processes' rings) are in the jobs' traces, and every new
    metric but the one that needs device time is on the line."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    seen = {}

    def look(cluster, state):
        seen["roles"] = job_trace.child_roles()
        seen["cluster"] = {"admin": [cluster.admin],
                           "volume": sorted(cluster.vol_urls)}
    # 4 s, not 2: beside five other test workers a toy job has taken
    # over 2 s, and one job is no second line to compare
    code = run.main(["--workload", CELL, "--seed", "2147484025",
                     "--seconds", "4", "--trace", "1", "--rehearse"],
                    run.Hooks(before_verify=look))
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    assert dict(seen["roles"], volume=sorted(seen["roles"]["volume"])) \
        == seen["cluster"]
    line = json.loads(out.strip().splitlines()[-1])
    # (a toy window may run out of volumes, `chain_dry_s`: the set-up's
    # job is no measure of a 0.3 s job; every other number is exact)
    assert all(c["value"] == 0 for n, c in line["compared"].items()
               if n != "chain_dry_s"), line["compared"]
    got = {n[len("rehearsal."):] for n in line["metrics"]}
    assert set(NEW) - {"enc_idle_h2d_share"} <= got
    assert "enc_idle_h2d_share" not in got and \
        "gf_encode_roofline" not in got
    m = {n[len("rehearsal."):]: v["value"]
         for n, v in line["metrics"].items()}
    assert m["job_distribute_s"] > 0 and m["push_GBps"] > 0
    assert 0 < m["push_sender_cpu_share"] < 1.5
    assert 0 < m["push_receiver_cpu_share"] < 1.5
    assert 0 < m["staging_pad_share"] < 1
    # constants since PR 29: no copy before a put, no semaphore, no
    # hand-off queue inside a launch
    assert m["staging_pack_share"] == 0
    assert m["staging_slot_wait_s"] == 0 and m["staging_ready_wait_s"] == 0
    # the phase's rate is no less than one stream's
    assert m["push_phase_GBps"] >= m["push_GBps"] * 0.99
    # the program's span agrees with the phase the harness cuts out of
    # the progress messages, job by job
    both = [ln for ln in out.splitlines() if "by progress marks" in ln]
    assert len(both) == line["attempted"] >= 2
    for ln in both:
        span_s = float(ln.split("ec.distribute ")[1].split("s ")[0])
        mark_s = float(ln.split("against ")[1].split("s ")[0])
        assert abs(span_s - mark_s) < 0.2, ln
