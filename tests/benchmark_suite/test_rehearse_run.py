"""Rehearsals of `ec6_3_serve.read_under_encode` end to end at toy
size (the job path in its burst, the RS(6,3) scheme carried by .vif,
the served EC reads, through a cluster of processes), and the same run
with the timed path broken underneath, which has to come out as not
correct.  The cell stood in benchmark/held_cells.json until PR 33;
`--held` is still exercised, on a checkout under `tmp_path` in which a
test holds the cell."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench_tree import bench_tree  # noqa: F401 — a fixture
from benchmark import faults, run

CELL = "ec6_3_serve.read_under_encode"
KEPT = "ec10_4_vol1g.encode"
LIVE = "ec10_4_live.encode_under_read"
TOY_MACHINE = 450 * 10**6     # two thirds of it hold three toy volumes
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("BENCH_RUN", None)


GUARD = {"read_rps", "read_p99_ms", "setup_s"}
BURST = {"bg_busy_share", "rd_p50_ms", "bg_encode_GBps",
         "rd_volume_request_ms", "rd_needle_cache_hit_share",
         "rd_generator_late_ms", "compiles_in_window.rd", "hb_errors.rd",
         "rd_remote_interval_share.rd"}


def test_rehearsal_prints_the_contracts_last_line():
    """Traced, as a process of its own; the untraced line, with the
    end-to-end metrics, is read in
    `test_a_held_cell_needs_asking_for_and_then_runs`."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "2147483999", "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=run.REPO, env=ENV,
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0, \
        f"{line['compared']}\n{p.stderr[-3000:]}"
    assert line["attempted"] > 100
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]     # no device, no device time
    got = {n[len("rehearsal."):]: v["value"]
           for n, v in line["metrics"].items()}
    assert len(got) == len(line["metrics"])   # each under `rehearsal.`
    assert set(got) == BURST                  # and no device metric
    assert 0 < got["bg_busy_share"] <= 1
    assert got["bg_encode_GBps"] > 0 and got["rd_p50_ms"] > 0
    for c in line["compared"].values():
        assert c["value"] == c["limit"] == 0
    assert "data root:" in p.stdout and " on " in p.stdout
    # the burst is said: its count, how many started, when the last ended
    said = re.search(r"burst: (\d) of 3 jobs started back to back from the "
                     r"opening, the last ended at \+([\d.]+)s", p.stdout)
    assert said and 1 <= int(said.group(1)) <= 3, p.stdout[-3000:]
    assert "wanted 3 (3s, a burst of 3, the configuration's " \
        "encode_burst_volumes)" in p.stdout
    assert "closed by seconds (3s)" in p.stdout and "A FAULT" not in p.stdout
    # every number compared stands beside its limit on stderr, last
    tail = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(ln.startswith("compared ") and "(limit 0)" in ln
               for ln in tail)


@pytest.mark.parametrize("fault,number", [
    ("flip_parity_byte", "parity_mismatch_bytes"),
    ("lose_shard", "shard_placement_faults"),
    ("alter_read_set", "requests_failed"),
    ("starve_chain", "chain_dry_s"),
])
def test_a_broken_timed_path_is_not_correct(fault, number, capfd,
                                            monkeypatch):
    """The harness's look for a chip skipped (--rehearse), the rest of
    a run driven with the fault planted underneath."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    hooks = faults.starve_chain(keep=0) if fault == "starve_chain" \
        else faults.FAULTS[fault]()
    code = run.main(["--workload", CELL, "--seed", "77", "--seconds", "2",
                     "--trace", "0", "--rehearse"], hooks)
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"][number]["value"] != 0


def test_no_chip_is_a_non_zero_exit_and_no_line():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=run.REPO, env=ENV, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_the_encode_cell_rehearses():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", KEPT,
         "--seed", "2147484001", "--seconds", "2", "--trace", "0",
         "--rehearse"], cwd=run.REPO, env=ENV, capture_output=True,
        text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"rehearsal.ec_GBps",
                                    "rehearsal.setup_s"}
    assert "shard_placement_faults" in line["compared"]
    # every job's own account of where its shards went is on the record
    assert p.stdout.count("distributed to 3 servers") >= 2


def test_a_held_cell_needs_asking_for_and_then_runs(bench_tree, capfd,
                                                    monkeypatch):
    """On a checkout in which the cell is held: not found without
    `--held` (exit 1, no line), found and run whole with it."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench_tree.hold(CELL)
    argv = ["--workload", CELL, "--seed", "2147484003", "--seconds", "2",
            "--trace", "0", "--rehearse"]
    assert run.main(argv, run.Hooks(root=bench_tree.root)) == 1
    got = capfd.readouterr()
    assert "no workload" in got.err
    assert not [ln for ln in got.out.splitlines() if ln.startswith("{")]
    assert run.main(argv + ["--held"], run.Hooks(root=bench_tree.root)) == 0
    out = capfd.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert set(line["metrics"]) == {"rehearsal." + n for n in GUARD}
    got = {n: v["value"] for n, v in line["metrics"].items()}
    assert got["rehearsal.read_rps"] > 10 and line["attempted"] > 100
    assert got["rehearsal.read_p99_ms"] > 0 and got["rehearsal.setup_s"] > 0
    assert "burst: " in out and " of 3 jobs started" in out


@pytest.mark.parametrize("vids,which", [
    ([7], {"volumeId": 7}), ([7, 9], {"volumeIds": [7, 9]}),
    ([3, 4, 5, 6], {"volumeIds": [3, 4, 5, 6]})])
def test_a_job_names_one_volume_or_several_of_one_collection(
        vids, which, monkeypatch):
    """`Cluster.submit_encode` takes a list: `volumeId` for one, as
    every cell sends today, `volumeIds` (the worker's batch path) for
    several, which `traffic/encode_4chip.json` asks for (`group_from`)."""
    from benchmark import cluster as cl
    from seaweedfs_tpu.server import httpd
    sent = []

    def http_json(method, url, body, **_kw):
        sent.append((method, url, body))
        return {"jobId": "j1"}
    monkeypatch.setattr(httpd, "http_json", http_json)
    c = cl.Cluster.__new__(cl.Cluster)
    c.admin, c.cfg = "http://admin", {"data_shards": 6, "parity_shards": 3}
    vols = [{"vid": v, "collection": "bench4"} for v in vids]
    assert c.submit_encode(vols) == "j1"
    assert sent == [("POST", "http://admin/maintenance/submit_job", {
        "jobType": "erasure_coding", "params": dict(
            which, collection="bench4", dataShards=6, parityShards=3)})]


@pytest.mark.parametrize("starved,closed,correct", [
    (False, "budget", True), (True, "seconds", False)])
def test_a_window_the_memory_budget_closes_is_whole_and_a_starved_one_not(
        starved, closed, correct, capfd, monkeypatch):
    """A machine of toy size handed in through `Hooks`: the window
    wants some hundred volumes, the budget leaves a few, a job starts
    on each and the window closes there, correct, `chain_dry_s` 0, the
    reads summed over the seconds the chain ran while the clients went
    on.  With volumes taken away after sizing (`starve_chain`) the
    same run is not correct: fewer started than the budget left."""
    import dataclasses
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    hooks = dataclasses.replace(
        faults.starve_chain(keep=1) if starved else run.Hooks(),
        memory_total=TOY_MACHINE)
    code = run.main(["--workload", LIVE, "--seed", "2147484077",
                     "--seconds", "8", "--trace", "0", "--rehearse"], hooks)
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    said = re.search(r"window's volumes: wanted (\d+), budget (\d+), "
                     r"loaded (\d+), started (\d+); closed by (\w+)", out)
    wanted, budget, loaded, started = (int(g) for g in said.groups()[:4])
    assert wanted > budget == loaded >= 2 and said.group(5) == closed
    assert line["correct"] is correct
    dry = line["compared"]["chain_dry_s"]["value"]
    assert "least MemAvailable" in out and "peak Shmem" in out
    if starved:
        assert started == 1 and dry > 0
        return
    assert started == budget and dry == 0
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert line["metrics"]["rehearsal.ec_GBps"]["value"] > 0
    ran = float(re.search(r"closed by budget after ([\d.]+)s", out).group(1))
    assert ran < 8 and "UNDER 20s" in out
    got = re.search(r"(\d+) requests \(0 failed, 0 wrong; (\d+) of them "
                    r"sent in the window's ([\d.]+)s\)", out)
    assert int(got.group(1)) > int(got.group(2)) > 0
    assert float(got.group(3)) == pytest.approx(ran, abs=0.3)


def test_a_machine_too_small_for_one_job_fails_in_set_up_with_both_numbers(
        capfd, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code = run.main(["--workload", KEPT, "--seed", "2147484078",
                     "--seconds", "2", "--trace", "0", "--rehearse"],
                    run.Hooks(memory_total=90 * 10**6))
    got = capfd.readouterr()
    assert code == 1
    assert not [ln for ln in got.out.splitlines() if ln.startswith("{")]
    assert "FAILED: 1 job volumes need" in got.err
    assert "of the memory budget and 60000000 bytes" in got.err
