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
    assert line["correct"] is True and line["failed"] == 0
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
