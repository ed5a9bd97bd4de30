"""One rehearsal of `ec6_3_serve.read_under_encode` end to end at toy
size (the job path, the RS(6,3) scheme carried by .vif, the served EC
reads, through a cluster of processes), and the same run with the timed
path broken underneath, which has to come out as not correct.  The cell
is held out of BENCHMARK.json for a fault of the program
(benchmark/held_cells.json), so these runs ask for it with `--held`;
the cell that BENCHMARK.json keeps is rehearsed beside it."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import faults, run

CELL = "ec6_3_serve.read_under_encode"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("BENCH_RUN", None)


def test_rehearsal_prints_the_contracts_last_line():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "2147483999", "--seconds", "3", "--trace", "1",
         "--rehearse", "--held"], cwd=run.REPO, env=ENV,
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
    spec = run.load_spec(held=True)
    from_trace = {m["name"] for m in spec["per_layer"]
                  if m["source"] == "device_trace"}
    assert line["metrics"], "the traced rehearsal read no per-layer metric"
    for name in line["metrics"]:
        assert name.startswith("rehearsal.")
        assert name[len("rehearsal."):] not in from_trace
    for c in line["compared"].values():
        assert c["value"] == c["limit"] == 0
    assert "data root:" in p.stdout and " on " in p.stdout
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
                     "--trace", "0", "--rehearse", "--held"], hooks)
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"][number]["value"] != 0


def test_no_chip_is_a_non_zero_exit_and_no_line():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0", "--held"],
        cwd=run.REPO, env=ENV, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


KEPT = "ec10_4_vol1g.encode"


def test_the_kept_cell_rehearses_and_a_held_one_needs_asking_for():
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
    q = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=run.REPO, env=ENV, capture_output=True, text=True, timeout=120)
    assert q.returncode != 0 and "no workload" in q.stderr
    assert not [ln for ln in q.stdout.splitlines() if ln.startswith("{")]


LIVE = "ec10_4_live.encode_under_read"
TOY_MACHINE = 450 * 10**6     # two thirds of it hold three toy volumes


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
    import re
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
