"""A job of several volumes (`ec10_4_batch.encode_4chip`): the group a
traffic file names, the memory budget with a whole group in flight, a
batch job's phases, the roofline over the cell's chips, and the three
readers of what four chips did, on tables made from the recorded
one-chip trace (benchmark/testdata)."""

import json
import os

import pytest

from benchmark import job_trace, run, trace_reduce as tr

CELL = "ec10_4_batch.encode_4chip"
TWIN = "ec10_4_vol1g.encode"
BENCH = os.path.join(run.REPO, "benchmark")
V1016 = 1016 * (1 << 20) + 8 + 1016 * 40      # a .dat of 1016 needles
HOST = 45 * 2**30                             # the one-chip machine's memory
RS10_4 = {"data_shards": 10, "parity_shards": 4}
RS6_3 = {"data_shards": 6, "parity_shards": 3}
PLANES = [f"/device:TPU:{i}" for i in range(4)]


def reader(name):
    return run.metric_reader(BENCH, name)


@pytest.fixture(scope="module")
def spec():
    return run.load_spec()


# -- the group ------------------------------------------------------------

@pytest.mark.parametrize("jobs,cfg,group", [
    ({"order": "back_to_back"}, {"batch_volumes": 4}, 1),   # not named
    ({"group_from": "batch_volumes"}, {"batch_volumes": 4}, 4),
    ({"group_from": "batch_volumes"}, {"batch_volumes": 1}, 1),
    ({"group_from": "per_job"}, {"per_job": 2, "batch_volumes": 4}, 2),
    ({"group_from": "batch_volumes"}, {}, "fails"),
    ({"group_from": "batch_volumes"}, {"batch_volumes": 0}, "fails"),
    ({"group_from": "batch_volumes"}, {"batch_volumes": 2.0}, "fails"),
    ({"group_from": "batch_volumes"}, {"batch_volumes": True}, "fails")])
def test_a_group_is_the_configurations_count_under_the_key_named(
        jobs, cfg, group):
    if group == "fails":
        with pytest.raises(run.BenchFailure, match="not a count of volumes"):
            run.group_of(cfg, jobs)
    else:
        assert run.group_of(cfg, jobs) == group


def test_only_the_batch_cells_traffic_names_a_group(spec):
    for w in spec["workloads"]:
        got = run.cell_files(spec, w["name"])
        group = run.group_of(got["cfg"], got["traffic"]["jobs"])
        assert group == (4 if w["name"] == CELL else 1)
        rehearsed = dict(got["cfg"], **got["cfg"]["rehearse"])
        assert run.group_of(rehearsed, got["traffic"]["rehearse"]["jobs"]) \
            == (2 if w["name"] == CELL else 1)


# -- the budget with a group in flight ----------------------------------------

@pytest.mark.parametrize("cfg,n", [
    (RS10_4, 1), (RS10_4, 18), (RS10_4, 19), (RS6_3, 1), (RS6_3, 16),
    (RS10_4, 0)])
def test_a_group_of_one_needs_what_one_volume_a_job_needed(cfg, n):
    """`g` = 1 is the formula the three accepted cells were sized by:
    (k+r)/k x (n - 1) + 2 + 2 (k+r)/k of a volume."""
    grow = (cfg["data_shards"] + cfg["parity_shards"]) / cfg["data_shards"]
    old = 0 if n <= 0 else V1016 * (grow * (n - 1) + 2 + 2 * grow)
    assert run.set_bytes(cfg, V1016, n) == run.set_bytes(cfg, V1016, n, 1)
    assert run.set_bytes(cfg, V1016, n) == pytest.approx(old, abs=1)


@pytest.mark.parametrize("cfg,n,group,volumes_worth", [
    (RS10_4, 4, 4, 19.2),      # one job in flight: 4 x 4.8
    (RS10_4, 8, 4, 24.8),      # and one at rest: + 4 x 1.4
    (RS10_4, 12, 4, 30.4),
    (RS10_4, 4, 2, 12.4),      # 2 x 4.8 + 2 x 1.4
    (RS6_3, 6, 3, 19.5),       # 3 x 5.0 + 3 x 1.5
    (RS10_4, 0, 4, 0.0)])
def test_a_whole_group_is_in_flight_at_the_worst_moment(cfg, n, group,
                                                        volumes_worth):
    """A job of several volumes pulls them all before it encodes and
    keeps source, copy and both sets of shard files of each until it
    ends; the jobs before it rest as their shards."""
    assert run.set_bytes(cfg, V1016, n, group) == pytest.approx(
        volumes_worth * V1016, abs=1)


@pytest.mark.parametrize("cfg,resident,group,n_budget", [
    (RS10_4, 0, 1, 16), (RS10_4, int(1.4 * V1016), 1, 15),
    (RS6_3, int(1.5 * V1016) + 3 * (30 << 20), 1, 14),   # as accepted
    (RS10_4, 0, 4, 8),                     # 26.42 GB of the 28.63
    (RS10_4, int(0.15e9), 4, 8),           # the toy set-up group at rest
    (RS10_4, int(2.1e9), 4, 8),
    (RS10_4, int(2.3e9), 4, 4),            # a whole group or none
    (RS10_4, int(9e9), 4, 0),
    (RS10_4, 0, 2, 14), (RS10_4, 0, 3, 9)])
def test_the_budget_holds_whole_groups(cfg, resident, group, n_budget):
    """Two jobs of four volumes of 1016 MiB under the one-chip
    machine's 28.63 GB, while under 2.2 GB is resident."""
    room = run.memory_budget(HOST) - resident
    n = run.volumes_within(cfg, V1016, room, group)
    assert n == n_budget and n % group == 0
    assert run.set_bytes(cfg, V1016, n, group) <= max(room, 0)
    assert run.set_bytes(cfg, V1016, n + group, group) > room


# -- a batch job's phases -----------------------------------------------------

BATCH_LOG = [
    ["", "trace_start", 9.0], ["a", "start", 10.0],
    ["a", "pulled volume 11 (1/4)", 10.9], ["a", "pulled volume 12 (2/4)",
                                            11.4],
    ["a", "pulled volume 13 (3/4)", 11.9], ["a", "pulled volume 14 (4/4)",
                                            12.5],
    ["a", "batch-encoded 4 volumes (jax on tpu TPU v5 lite x4)", 20.0],
    ["a", "distributed volume 11 (1/4)", 20.7],
    ["a", "distributed volume 12 (2/4)", 21.4],
    ["a", "distributed volume 13 (3/4)", 22.0],
    ["a", "distributed volume 14 (4/4)", 22.75], ["a", "end", 23.0],
    ["b", "start", 23.1], ["b", "pulled volume 15 (1/4)", 23.6],
    # one volume a job, as the three accepted cells' jobs report
    ["c", "start", 30.0], ["c", "marked readonly", 30.1],
    ["c", "copied volume files", 30.6],
    ["c", "encoded 14 shards (jax on tpu)", 31.8],
    ["c", "distributed shards", 32.5], ["c", "end", 32.6]]


def test_a_batch_jobs_phases_run_to_the_last_report_of_their_kind():
    ph = run.job_phases(BATCH_LOG)
    assert ph["a"]["phases"] == {"pull": (10.0, 12.5),
                                 "encode": (12.5, 20.0),
                                 "distribute": (20.0, 22.75),
                                 "finish": (22.75, 23.0)}
    assert ph["a"]["start"] == 10.0 and ph["a"]["end"] == 23.0
    # a job cut off in its pulls has that phase so far and no other
    assert ph["b"]["phases"] == {"pull": (23.1, 23.6)}
    assert ph["b"]["end"] is None
    # the single job's marks read as they did
    assert ph["c"]["phases"] == {"pull": (30.1, 30.6),
                                 "encode": (30.6, 31.8),
                                 "distribute": (31.8, 32.5),
                                 "finish": (32.5, 32.6)}


def test_idle_gaps_name_a_batch_jobs_phases():
    ph = run.job_phases(BATCH_LOG)["a"]["phases"]
    spans = [(n, s, e) for n, (s, e) in ph.items()]
    # idle is where no chip works: 15.0-15.5, 16.0-16.5, ... leave 5.5 s
    busy = {p: [(15.0 + i, 15.5 + i)] for i, p in enumerate(PLANES)}
    gaps = dict(tr.gaps_by_phase(busy, spans, 9.5, 23.5))
    assert set(gaps) == {"pull", "encode", "distribute", "finish",
                         "between_jobs"}
    assert gaps["pull"] == pytest.approx(2.5)
    assert gaps["encode"] == pytest.approx(7.5 - 4 * 0.5)
    assert gaps["between_jobs"] == pytest.approx(1.0)


# -- four planes made from the recorded one --------------------------------

@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(BENCH, "testdata",
                           "trace_v5e_one_job.json")) as f:
        return json.load(f)


def four_planes(rec, shares):
    """The recorded plane's operations dealt out to four planes: plane
    i gets every operation whose turn modulo len(shares) it names."""
    ev = rec["events"]
    ops = ev["devices"]["/device:TPU:0"]
    planes = {p: [] for p in PLANES}
    for turn, op in enumerate(ops):
        planes[PLANES[shares[turn % len(shares)]]].append(op)
    return {"devices": planes, "sync": ev["sync"]}


def ctx_of(rec, events, chips):
    busy = tr.busy_by_device(events, rec["open"], rec["close"])
    return {"cfg": RS10_4, "device": {"kind": "TPU v5 lite"},
            "chips": chips, "compile": {}, "staging": {},
            "jobs": [{"id": "j", "ok": True, "bytes": 1065394168}],
            "trace": {"busy": busy, "busy_s": tr.busy_seconds(busy),
                      "window_s": rec["close"] - rec["open"]}}


def test_the_roofline_at_one_chip_is_the_number_it_was(rec):
    one = ctx_of(rec, rec["events"], 1)
    old = 100.0 * (tr.encode_min_bytes(1065394168, 10, 4) / 819e9) \
        / one["trace"]["busy_s"]      # as PR 33 computed it
    assert reader("gf_encode_roofline")(one) == old       # to the digit
    assert reader("gf_encode_roofline.live")(one) == old
    del one["chips"]          # a context recorded before this PR
    assert reader("gf_encode_roofline")(one) == old
    assert tr.roofline_share(1e9, 0.01, "TPU v5 lite") == \
        tr.roofline_share(1e9, 0.01, "TPU v5 lite", 1)


@pytest.mark.parametrize("deal", [[0, 1, 2, 3], [0, 0, 1, 2, 3, 3],
                                  [0, 0, 0, 0]])
def test_the_roofline_is_over_the_cells_chips_wherever_the_work_went(
        rec, deal):
    """The same operations on four planes: the least time is four
    chips' together and the busy time their mean, so the share reads
    what the one chip read, not four times that."""
    one = reader("gf_encode_roofline")(ctx_of(rec, rec["events"], 1))
    four = ctx_of(rec, four_planes(rec, deal), 4)
    assert sorted(four["trace"]["busy"]) == PLANES
    assert reader("gf_encode_roofline.batch")(four) == pytest.approx(
        one, rel=0.05)            # the recorded operations overlap a little
    assert reader("gf_encode_roofline.batch")(four) < 100


def test_four_chips_that_split_the_least_bytes_perfectly_read_100():
    dat, window = 8 * 1065394168, 30.0
    least_s = dat * 1.4 / 819e9 / 4          # each chip a quarter, at peak
    busy = {p: [(100.0 + i, 100.0 + i + least_s)]
            for i, p in enumerate(PLANES)}
    ctx = {"cfg": RS10_4, "device": {"kind": "TPU v5 lite"}, "chips": 4,
           "jobs": [{"id": "j", "ok": True, "bytes": dat}],
           "trace": {"busy": busy, "busy_s": tr.busy_seconds(busy),
                     "window_s": window}}
    assert reader("gf_encode_roofline.batch")(ctx) == pytest.approx(100.0)
    # each chip sent every byte (the data replicated) reads a quarter
    busy = {p: [(100.0, 100.0 + 4 * least_s)] for p in PLANES}
    ctx["trace"].update(busy=busy, busy_s=tr.busy_seconds(busy))
    assert reader("gf_encode_roofline.batch")(ctx) == pytest.approx(25.0)


@pytest.mark.parametrize("deal,chips,skew", [
    ([0, 1, 2, 3], 4, "even"), ([0, 0, 0, 1], 2, "skewed"),
    ([2, 2, 2, 2], 1, "one")])
def test_the_chips_that_worked_and_the_busiest_of_them(rec, deal, chips,
                                                       skew):
    ctx = ctx_of(rec, four_planes(rec, deal), 4)
    window = ctx["trace"]["window_s"]
    per = {p: sum(e - s for s, e in iv)
           for p, iv in ctx["trace"]["busy"].items()}
    assert reader("chips_busy.batch")(ctx) == chips
    top = reader("chip_busy_max_share.batch")(ctx)
    assert top == pytest.approx(max(per.values()) / window)
    mean_busy = 1 - reader("device_idle_share.batch")(ctx)
    assert mean_busy == pytest.approx(sum(per.values()) / 4 / window)
    if skew == "even":
        assert top < 1.3 * mean_busy
    elif skew == "one":
        assert top == pytest.approx(4 * mean_busy)
    else:
        assert 2 * mean_busy < top < 4 * mean_busy


def test_a_jobs_pulls_are_summed_and_the_jobs_meaned():
    ctx = {"jobs": [{"id": "j1", "ok": True}, {"id": "j2", "ok": True}]}

    def pull(i, ms):
        return {"spanId": f"p{i}", "name": "ec.pull", "role": "worker",
                "start": float(i), "durationMs": ms}
    other = {"spanId": "e", "name": "ec.encode", "role": "worker",
             "start": 9.0, "durationMs": 7000.0}
    job_trace.preload(ctx, [[pull(i, 500.0) for i in range(4)] + [other],
                            [pull(i + 4, 750.0) for i in range(4)]])
    assert reader("job_pull_s.batch")(ctx) == pytest.approx(2.5)
    job_trace.preload(ctx, [[other], []])     # a program with no such span
    assert reader("job_pull_s.batch")(ctx) is None


@pytest.mark.parametrize("name", ["chips_busy.batch",
                                  "chip_busy_max_share.batch",
                                  "gf_encode_roofline.batch",
                                  "device_idle_share.batch"])
def test_no_trace_no_device_number(name):
    ctx = {"trace": None, "cfg": RS10_4, "chips": 4,
           "device": {"kind": "TPU v5 lite"},
           "jobs": [{"id": "j", "ok": True, "bytes": 10}]}
    assert reader(name)(ctx) is None


# -- the entries --------------------------------------------------------------

BATCH_LAYER = {   # name: (unit, better, source, layer)
    "job_encode_s.batch": ("s", "lower", "program_span", "EC file pipeline"),
    "job_distribute_s.batch": ("s", "lower", "program_span",
                               "maintenance plane"),
    "push_phase_GBps.batch": ("GB/s", "higher", "program_span",
                              "maintenance plane"),
    "push_receiver_cpu_share.batch": ("share", "lower", "program_span",
                                      "serving planes"),
    "gf_encode_roofline.batch": ("%", "higher", "device_trace", "kernels"),
    "device_idle_share.batch": ("share", "lower", "device_trace", "device"),
    "compiles_in_window.batch": ("count", "lower", "program_counter",
                                 "device selection"),
    "job_pull_s.batch": ("s", "lower", "program_span", "maintenance plane"),
    "chips_busy.batch": ("count", "higher", "device_trace", "device"),
    "chip_busy_max_share.batch": ("share", "lower", "device_trace",
                                  "device"),
}


@pytest.mark.parametrize("name", sorted(BATCH_LAYER))
def test_the_batch_cells_per_layer_entries(spec, name):
    m = {e["name"]: e for e in spec["per_layer"]}[name]
    assert (m["unit"], m["better"], m["source"], m["layer"]) == \
        BATCH_LAYER[name]
    assert m["workloads"] == [CELL] and m["moves"] == "ec_GBps"
    assert callable(reader(name))
    assert m in run.metrics_of(spec, "per_layer", CELL)
    # a layer PERF.md 3 and the other cells' entries already name
    assert m["layer"] in {e["layer"] for e in spec["per_layer"]
                          if CELL not in e["workloads"]}


def test_the_batch_cell_has_those_ten_and_the_twin_its_own(spec):
    layer = run.metrics_of(spec, "per_layer", CELL)
    assert sorted(m["name"] for m in layer) == sorted(BATCH_LAYER)
    assert {m["name"] for m in run.metrics_of(spec, "end_to_end", CELL)} \
        == {"ec_GBps", "setup_s"}
    assert not any(m["name"].endswith(".batch")
                   for m in run.metrics_of(spec, "per_layer", TWIN))


def test_the_configuration_is_the_twins_in_groups_of_four(spec):
    got, twin = run.cell_files(spec, CELL), run.cell_files(spec, TWIN)
    cfg, base = got["cfg"], twin["cfg"]
    for key in ("data_shards", "parity_shards", "needles_per_volume",
                "needle_bytes", "volume_size_limit_mb", "volume_servers",
                "shard_spread", "backend", "reference"):
        assert cfg[key] == base[key], key
    assert cfg["batch_volumes"] == 4
    assert cfg["reduced"] == ["volume_size_limit_mb", "batch_volumes"]
    assert cfg["published"] == {"volume_size_limit_mb": 30000,
                                "batch_volumes": 64, "chips": 8}
    assert len(cfg["guarantees"]) == len(base["guarantees"]) == 4
    assert "configs[2]" in cfg["source"]
    conf = {c["name"]: c for c in spec["configs"]}["ec10_4_batch"]
    assert conf["reduced"] == cfg["reduced"]
    assert "configs[2]" in conf["source"] and len(conf["source"]) <= 200
    assert cfg["rehearse"]["batch_volumes"] == 2
    jobs = got["traffic"]["jobs"]
    assert jobs == dict(twin["traffic"]["jobs"], group_from="batch_volumes")
    assert got["traffic"]["verify"] == twin["traffic"]["verify"]
    assert got["traffic"]["reads"] is None
