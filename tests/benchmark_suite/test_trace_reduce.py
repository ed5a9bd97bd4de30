"""The trace reduction on a small recorded trace: one job of
`ec10_4_vol1g.encode` on a TPU v5 lite (benchmark/testdata)."""

import json
import os

import pytest

from bench_tree import spec_tree  # noqa: F401 — a fixture
from benchmark import run, trace_reduce as tr

PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(run.HERE, "testdata",
                           "trace_v5e_one_job.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def busy(rec):
    return tr.busy_by_device(rec["events"], rec["open"], rec["close"])


def sweep(rec):
    """Busy seconds a second way: walk the sorted event edges."""
    off = tr.host_offset_ns(rec["events"]["sync"])
    edges = []
    for _n, s, d in rec["events"]["devices"][PLANE]:
        a = max((s + off) / 1e9, rec["open"])
        b = min((s + d + off) / 1e9, rec["close"])
        if b > a:
            edges += [(a, 1), (b, -1)]
    depth, since, total = 0, 0.0, 0.0
    for t, step in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            total += t - since
    return total


def test_union_merges_touching_and_nested_intervals():
    assert tr.union([(3, 4), (0, 1), (1, 2), (0.5, 0.6), (5, 5)]) == \
        [(0, 2), (3, 4)]
    assert tr.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]


def test_busy_union_of_the_recorded_job(rec, busy):
    got = tr.busy_seconds(busy)
    assert got == pytest.approx(sweep(rec), rel=1e-9)
    assert 0.05 < got < 0.09            # ~66 ms of kernels in a 12.8 s job
    raw = sum(d for _n, _s, d in rec["events"]["devices"][PLANE]) / 1e9
    assert got <= raw + 1e-9            # a union never exceeds the sum
    iv = busy[PLANE]
    assert all(a < b for a, b in iv)
    assert all(iv[i][1] < iv[i + 1][0] for i in range(len(iv) - 1))


def test_idle_share(rec, busy):
    window = rec["close"] - rec["open"]
    idle = tr.idle_share(tr.busy_seconds(busy), window)
    assert idle == pytest.approx(1 - sweep(rec) / window)
    assert 0.99 < idle < 1.0
    assert tr.idle_share(None, window) is None


def test_gaps_are_named_by_the_job_phase_and_add_up(rec, busy):
    phases = [(n, s, e) for j in run.job_phases(rec["log"]).values()
              for n, (s, e) in j["phases"].items()]
    gaps = dict(tr.gaps_by_phase(busy, phases, rec["open"], rec["close"]))
    assert max(gaps, key=gaps.get) == "distribute"
    assert gaps["distribute"] > gaps["encode"] > gaps["pull"]
    assert "between_jobs" in gaps
    window = rec["close"] - rec["open"]
    assert sum(gaps.values()) + tr.busy_seconds(busy) == \
        pytest.approx(window, rel=1e-6)
    # the device works only while the host is in `encode`
    enc = next((s, e) for n, s, e in phases if n == "encode")
    assert all(enc[0] <= a and b <= enc[1] for a, b in busy[PLANE])


def test_top_ops_names_are_cut_to_the_operation(rec):
    ops = tr.top_ops(rec["events"], rec["open"], rec["close"], n=5)
    assert len(ops) == 5
    assert [s for _n, s in ops] == sorted((s for _n, s in ops),
                                          reverse=True)
    assert all(" " not in n and len(n) <= 80 for n, _s in ops)
    assert tr.op_name("%multiply_xor_fusion.6 = u32[1,8]{1,0} fusion("
                      "u32[] %bitcast.79)") == "multiply_xor_fusion.6"


def test_roofline_from_bytes_and_busy_time(rec, busy):
    dat = 1065394168            # one 1016 x 1 MiB volume's .dat
    least = tr.encode_min_bytes(dat, 10, 4)
    assert least == pytest.approx(dat * 1.4)
    busy_s = tr.busy_seconds(busy)
    share = tr.roofline_share(least, busy_s, "TPU v5 lite")
    assert share == pytest.approx(100 * (least / 819e9) / busy_s)
    assert 1.5 < share < 5.0            # PR 24 read 2.7 %
    assert tr.roofline_share(least, None, "TPU v5 lite") is None
    assert tr.roofline_share(0, busy_s, "TPU v5 lite") is None


@pytest.mark.parametrize("kind", ["TPU v9 imaginary", "cpu", "_source", ""])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(tr.UnknownDevice):
        tr.peaks_for(kind)
    with pytest.raises(tr.UnknownDevice):
        tr.roofline_share(1e9, 1.0, kind)


def test_a_reader_that_finds_nothing_returns_nothing(rec, spec_tree):
    unsynced = {"devices": rec["events"]["devices"], "sync": []}
    assert tr.busy_by_device(unsynced, rec["open"], rec["close"]) == {}
    assert tr.busy_seconds({}) is None
    assert tr.top_ops(unsynced, rec["open"], rec["close"]) == []
    assert tr.gaps_by_phase({}, [], 0.0, 1.0) == []
    ctx = {"trace": None, "staging": {}, "compile": {}, "jobs": [],
           "reads": None, "volume_counters": None,
           "cfg": {"data_shards": 10, "parity_shards": 4},
           "device": {"kind": "TPU v5 lite"}}
    for m in spec_tree.spec["per_layer"]:
        read = run.metric_reader(spec_tree.bench, m["name"])
        assert read(ctx) is None, m["name"]


def test_readers_on_the_recorded_job(rec, busy):
    ph = run.job_phases(rec["log"])
    job = next(dict(j, spans=[
        {"name": "ec.encode", "start": j["start"] + 1, "durationMs": 2600.0,
         "busySeconds": None},
        {"name": "encode.write", "start": j["start"] + 1,
         "durationMs": 2500.0, "busySeconds": 1.9}])
        for j in ph.values() if j["phases"])
    job.update(ok=True, bytes=1065394168)
    ctx = {"cfg": {"data_shards": 10, "parity_shards": 4},
           "device": {"kind": "TPU v5 lite"}, "jobs": [job],
           "staging": {"h2d_bytes": 1342177280, "h2d_seconds": 1.6,
                       "overlap_numer": 0.1, "overlap_denom": 0.25},
           "compile": {"compiled": 0, "requests": 0},
           "trace": {"busy_s": tr.busy_seconds(busy), "busy": busy,
                     "window_s": rec["close"] - rec["open"]},
           "reads": None, "volume_counters": {
               "req_s": 50.0, "req_n": 2000.0, "cache_hits": 300.0,
               "cache_misses": 1700.0}}
    bench = os.path.join(run.REPO, "benchmark")

    def read(name):
        return run.metric_reader(bench, name)(ctx)
    assert read("job_encode_s") == pytest.approx(2.6)
    assert read("enc_write_busy_s") == pytest.approx(1.9)
    assert read("staged_h2d_GBps") == pytest.approx(1342177280 / 1.6 / 1e9)
    assert read("staging_overlap_fraction") == pytest.approx(0.4)
    # from the volume's bytes, whatever the program says it launched
    assert read("gf_encode_roofline") == pytest.approx(
        100 * (1065394168 * 1.4 / 819e9) / ctx["trace"]["busy_s"])
    ctx["staging"]["h2d_bytes"] *= 2
    assert 1.5 < read("gf_encode_roofline") < 5.0
    assert read("staging_launch_ratio") == pytest.approx(
        2 * 1342177280 / 1065394168)
    # one reader serves a quantity split by the metric it moves
    assert 0.99 < read("device_idle_share.enc") < 1.0
    assert read("device_idle_share.rd") == read("device_idle_share.enc")
    assert read("compiles_in_window.enc") == 0.0
    assert read("rd_volume_request_ms") == pytest.approx(25.0)
    assert read("rd_needle_cache_hit_share") == pytest.approx(0.15)
