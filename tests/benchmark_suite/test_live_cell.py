"""`ec10_4_live.encode_under_read` (ISSUE 27): the cell rehearsed end
to end at toy size, plain and traced; the same run with its read set
altered underneath, which has to come out as not correct; each reader
this cell brings on a recorded context and on one of the parent
commit's, where it has nothing to read; and the entries `BENCHMARK.json`
gained, each found by its name wherever it stands."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import faults, job_trace, role_metrics, run

CELL = "ec10_4_live.encode_under_read"
TWIN = "ec10_4_vol1g.encode"
BENCH = os.path.join(run.REPO, "benchmark")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("BENCH_RUN", None)
REHEARSAL_TIMEOUT_S = 240

# name: (unit, better, source, layer, the reader's file)
NEW = {
    "job_encode_s.live": ("s", "lower", "program_span",
                          "EC file pipeline", "job_encode_s"),
    "job_distribute_s.live": ("s", "lower", "program_span",
                              "maintenance plane", "job_distribute_s"),
    "push_GBps.live": ("GB/s", "higher", "program_span",
                       "maintenance plane", "push_GBps"),
    "push_receiver_cpu_share.live": ("share", "lower", "program_span",
                                     "serving planes",
                                     "push_receiver_cpu_share"),
    "gf_encode_roofline.live": ("%", "higher", "device_trace", "kernels",
                                "gf_encode_roofline"),
    "device_idle_share.live": ("share", "lower", "device_trace", "device",
                               "device_idle_share"),
    "compiles_in_window.live": ("count", "lower", "program_counter",
                                "device selection", "compiles_in_window"),
    "rd_volume_request_ms.live": ("ms", "lower", "program_counter",
                                  "serving planes", "rd_volume_request_ms"),
    "rd_needle_cache_hit_share.live": ("share", "higher", "program_counter",
                                       "serving planes",
                                       "rd_needle_cache_hit_share"),
    "rd_rps": ("1/s", "higher", "host_clock", "serving planes", "rd_rps"),
    "rd_p99_ms": ("ms", "lower", "host_clock", "serving planes",
                  "rd_p99_ms"),
    "rd_remote_interval_share": ("share", "lower", "program_counter",
                                 "serving planes",
                                 "rd_remote_interval_share"),
    "hb_errors": ("count", "lower", "program_counter", "serving planes",
                  "hb_errors"),
    "enc_write_busy_s.live": ("s", "lower", "program_span",
                              "EC file pipeline", "enc_write_busy_s"),
    "staging_overlap_fraction.live": ("share", "higher", "program_counter",
                                      "staging",
                                      "staging_overlap_fraction"),
    "push_sender_cpu_share.live": ("share", "lower", "program_span",
                                   "maintenance plane",
                                   "push_sender_cpu_share"),
    "enc_idle_h2d_share.live": ("share", "lower", "device_trace",
                                "EC file pipeline", "enc_idle_h2d_share"),
    "staging_pad_share.live": ("share", "lower", "program_counter",
                               "staging", "staging_pad_share"),
    "push_phase_GBps.live": ("GB/s", "higher", "program_span",
                             "maintenance plane", "push_phase_GBps"),
}
# what `ec10_4_vol1g.encode` reports (PR 24's nine and PR 25's nine,
# less `job_copy_share`, which PR 33 pruned, and PR 33's one):
# name: (unit, better, source, layer)
OLDER = {
    "job_encode_s": ("s", "lower", "program_span", "EC file pipeline"),
    "enc_write_busy_s": ("s", "lower", "program_span", "EC file pipeline"),
    "staged_h2d_GBps": ("GB/s", "higher", "program_counter", "staging"),
    "staging_overlap_fraction": ("share", "higher", "program_counter",
                                 "staging"),
    "staging_launch_ratio": ("x", "lower", "program_counter", "staging"),
    "gf_encode_roofline": ("%", "higher", "device_trace", "kernels"),
    "device_idle_share.enc": ("share", "lower", "device_trace", "device"),
    "compiles_in_window.enc": ("count", "lower", "program_counter",
                               "device selection"),
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
# the twin's metrics of layers this cell runs too that it does not
# report, each to be retired (PERF.md 7): two say what
# `staging_pad_share` says, three are constants since PR 29 whose
# `.live` entries PR 33 pruned
LEFT_TO_THE_TWIN = {"staged_h2d_GBps", "staging_launch_ratio",
                    "staging_pack_share", "staging_slot_wait_s",
                    "staging_ready_wait_s"}


def reader(name):
    return run.metric_reader(BENCH, name)


# -- the entries ----------------------------------------------------------

def named(spec: dict, key: str) -> dict:
    names = [e["name"] for e in spec[key]]
    assert len(set(names)) == len(names)
    return dict(zip(names, spec[key]))


def test_the_cells_entries_are_all_there_and_use_no_name_of_the_guards():
    spec = run.load_spec()
    per_layer = named(spec, "per_layer")
    e2e = {m["name"] for m in run.metrics_of(spec, "end_to_end", CELL)}
    assert e2e == {"ec_GBps", "setup_s"}
    layers = {m["layer"] for n, m in per_layer.items() if n not in NEW}
    for name, (unit, better, source, layer, file) in NEW.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (unit, better, source, layer)
        assert CELL in m["workloads"] and TWIN not in m["workloads"]
        assert m["moves"] == "ec_GBps" and m["layer"] in layers
        assert os.path.exists(os.path.join(BENCH, "metrics", file + ".py"))
        assert callable(reader(name))
    assert {m["name"] for m in run.metrics_of(spec, "per_layer", CELL)} \
        >= set(NEW)
    cfg = named(spec, "configs")["ec10_4_live"]
    assert cfg["reduced"] == ["read_objects"]
    cell = named(spec, "workloads")[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ec10_4_live", "encode_under_read", 1)
    # what the counters cover and what nothing guards is said up front
    assert "unguarded" in cell["why"] and "cover the run" in cell["why"]
    # nor a name of the guard's, which moves another end-to-end metric
    guard = {m["name"] for m in run.metrics_of(
        spec, "per_layer", "ec6_3_serve.read_under_encode")}
    assert not (set(NEW) | {CELL, "ec10_4_live"}) & guard


def test_the_twins_entries_stand_as_they_were():
    """What the idle cluster's cell reported it still reports, under
    the same names, units and readers; and this cell reports each of
    them under `.live`, but for those left to the twin."""
    spec = run.load_spec()
    per_layer = named(spec, "per_layer")
    for name, (unit, better, source, layer) in OLDER.items():
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (unit, better, source, layer)
        assert TWIN in m["workloads"] and CELL not in m["workloads"]
        assert m["moves"] == "ec_GBps"
        stem = name[:-len(".enc")] if name.endswith(".enc") else name
        assert os.path.exists(os.path.join(BENCH, "metrics", stem + ".py"))
        assert stem + ".live" in NEW or stem in LEFT_TO_THE_TWIN, name
    assert {m["name"] for m in run.metrics_of(spec, "per_layer", TWIN)} \
        >= set(OLDER)
    rate = named(spec, "end_to_end")["ec_GBps"]
    assert {TWIN, CELL} <= set(rate["workloads"]) and rate["bound"] == 0.1


def test_the_configuration_is_the_twins_with_readers():
    """Same cluster, same scheme, same job volumes as `ec10_4_vol1g`:
    the two cells differ in the readers alone."""
    spec = run.load_spec()
    got = run.cell_files(spec, CELL)
    twin = run.cell_files(spec, TWIN)
    for key in ("data_shards", "parity_shards", "volume_size_limit_mb",
                "needles_per_volume", "needle_bytes", "volume_servers",
                "shard_spread", "backend"):
        assert got["cfg"][key] == twin["cfg"][key], key
    cfg = got["cfg"]
    assert (cfg["read_volumes"], cfg["read_object_bytes"],
            cfg["read_objects"]) == (3, 1024, 60000)
    assert cfg["reduced"] == ["read_objects"]
    assert set(twin["cfg"]["guarantees"]) < set(cfg["guarantees"])
    assert "no request fails" in cfg["guarantees"]
    assert "maintenance_scripts" in cfg["assumed"]
    t = got["traffic"]
    assert t["jobs"]["role"] == "foreground"
    assert t["reads"] == {"processes": 2, "threads_per_process": 8,
                          "keys": "uniform", "timeout_s": 30.0}
    assert t["verify"]["needles_per_volume"] == 64
    assert t["rehearse"]["jobs"]["job_seconds_margin"] == 0.15


# -- the readers on a recorded context --------------------------------------

READS = {"requests": 15037, "wrong": 0, "failed": 0,
         "completed_in_window": 15021, "read_rps": 300.42,
         "read_p50_ms": 46.1, "read_p99_ms": 181.7,
         "late_mean_ms": 0.03, "late_max_ms": 0.4}


def volume_scrape(local, remote, rebuilt, beats, errors=None):
    fam = {
        "seaweedfs_tpu_ec_read_intervals_total": [
            ({"source": "local"}, local), ({"source": "remote"}, remote)]
        + ([({"source": "reconstructed"}, rebuilt)] if rebuilt else []),
        "seaweedfs_tpu_volume_heartbeat_seconds_bucket": [
            ({"le": "0.005"}, beats - 1), ({"le": "0.01"}, beats),
            ({"le": "+Inf"}, beats)],
        "seaweedfs_tpu_volume_heartbeat_seconds_sum": [({}, 0.002 * beats)],
        "seaweedfs_tpu_volume_heartbeat_seconds_count": [({}, beats)]}
    if errors:
        fam["seaweedfs_tpu_volume_heartbeat_errors_total"] = [
            ({"error": k}, v) for k, v in errors.items()]
    return fam


def live_ctx(**over):
    return dict({"cfg": {"volume_servers": 3}, "reads": dict(READS),
                 "jobs": []}, **over)


@pytest.mark.parametrize("name,want", [("rd_rps", 300.42),
                                       ("rd_p99_ms", 181.7)])
def test_the_clients_own_numbers_come_from_the_load_children(name, want):
    assert reader(name)(live_ctx()) == want
    assert reader(name)(live_ctx(reads=None)) is None


def test_the_remote_share_sums_the_volume_roles(capsys):
    ctx = live_ctx()
    role_metrics.preload(ctx, {"master": [{}], "volume": [
        volume_scrape(1800, 3500, 0, 150), volume_scrape(1700, 3600, 0, 150),
        volume_scrape(1500, 3900, 0, 150)]})
    assert reader("rd_remote_interval_share")(ctx) == \
        pytest.approx(11000 / 16000)
    assert "'local': 5000, 'remote': 11000" in capsys.readouterr().out


@pytest.mark.parametrize("errors,moves,want", [
    (None, None, 0.0),
    ({"RuntimeError": 1.0}, {"dead": 1.0, "alive": 1.0}, 2.0),
    ({"OSError": 3.0}, {"alive": 1.0}, 3.0),
])
def test_hb_errors_sums_the_beats_that_failed_and_the_servers_let_go(
        errors, moves, want, capsys):
    ctx = live_ctx()
    master = {} if moves is None else {
        "seaweedfs_tpu_master_node_transitions_total": [
            ({"to": k}, v) for k, v in moves.items()]}
    role_metrics.preload(ctx, {"master": [master], "volume": [
        volume_scrape(1, 1, 0, 140, errors), volume_scrape(1, 1, 0, 150),
        volume_scrape(1, 1, 0, 160)]})
    assert reader("hb_errors")(ctx) == want
    said = capsys.readouterr().out
    assert "450 timed, mean 2.00 ms, the slowest under 0.01 s" in said


@pytest.mark.parametrize("name", ["rd_remote_interval_share", "hb_errors"])
def test_a_counter_reader_returns_nothing_on_the_parents_roles(name):
    """The parent commit's roles have no such family on /metrics: the
    line then leaves the metric out, and nothing raises."""
    ctx = live_ctx()
    parent = {"volume_server_request_seconds_count": [
        ({"method": "GET", "code": "200"}, 15000.0)],
        "seaweedfs_tpu_ec_degraded_reads_total": [({"vid": "3"}, 0.0)]}
    role_metrics.preload(ctx, {"master": [{}],
                               "volume": [parent, parent, parent]})
    assert reader(name)(ctx) is None


def test_the_roles_are_found_by_their_command_lines():
    py = ["/usr/bin/python3", "-m", "seaweedfs_tpu"]
    argvs = [py + ["master", "-port", "9333", "-mdir", "/d/master"],
             py + ["volume", "-port", "8080", "-dir", "/d/vol0",
                   "-mserver", "127.0.0.1:9333"],
             py + ["volume", "-port", "8081", "-dir", "/d/vol1",
                   "-mserver", "127.0.0.1:9333"],
             py + ["admin", "-port", "23646", "-master", "127.0.0.1:9333"],
             ["/usr/bin/python3", "-m", "benchmark.worker_proc",
              "--master", "127.0.0.1:9333"], ["sleep", "1"], [""]]
    assert role_metrics.addresses(argvs) == {
        "master": ["127.0.0.1:9333"],
        "volume": ["127.0.0.1:8080", "127.0.0.1:8081"]}
    assert role_metrics.master_address(
        ["python3", "seaweedfs_tpu", "master", "-port", "1"]) is None
    assert role_metrics.master_address(
        py + ["master", "-port", "x"]) is None


def test_a_role_that_is_not_there_is_an_error_with_its_reason():
    role_metrics._cache.clear()
    with pytest.raises(job_trace.TraceUnreachable, match="1 master and 3"):
        role_metrics.scraped(live_ctx())     # this process has no roles


# -- the cell, rehearsed -----------------------------------------------------

def rehearse(*extra, seed="2147484127"):
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", seed, "--seconds", "2", "--rehearse", *extra],
        cwd=run.REPO, env=ENV, capture_output=True, text=True,
        timeout=REHEARSAL_TIMEOUT_S)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-3000:]
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_and_reports_its_two_end_to_end_metrics():
    p, line = rehearse("--trace", "0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"rehearsal.ec_GBps",
                                    "rehearsal.setup_s"}
    assert line["metrics"]["rehearsal.ec_GBps"]["value"] > 0
    assert {"bodies_wrong", "requests_failed", "shard_placement_faults",
            "parity_mismatch_bytes", "chain_dry_s"} <= set(line["compared"])
    for c in line["compared"].values():
        assert c["value"] == c["limit"] == 0
    assert line["attempted"] > 100     # jobs and the clients' requests
    # the jobs ran beside the readers, each placed on all three servers
    assert p.stdout.count("distributed to 3 servers") >= 5
    assert "master held alive" not in p.stdout


def test_the_traced_rehearsal_reads_every_metric_that_needs_no_device():
    p, line = rehearse("--trace", "1", seed="3147484127")
    assert line["correct"] is True
    want = {"rehearsal." + n for n, m in NEW.items()
            if m[2] != "device_trace"}
    assert set(line["metrics"]) == want
    value = {k[len("rehearsal."):]: v["value"]
             for k, v in line["metrics"].items()}
    assert value["hb_errors"] == 0 and value["compiles_in_window.live"] == 0
    assert 0 <= value["rd_remote_interval_share"] <= 1
    assert value["rd_rps"] > 10 and value["rd_p99_ms"] > 0
    assert "heartbeats over the run:" in p.stdout
    assert "ec read intervals over the run:" in p.stdout


def test_an_altered_read_set_is_not_correct(capfd, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code = run.main(["--workload", CELL, "--seed", "79", "--seconds", "2",
                     "--trace", "0", "--rehearse"],
                    faults.alter_read_set())
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["requests_failed"]["value"] + \
        line["compared"]["bodies_wrong"]["value"] > 0
