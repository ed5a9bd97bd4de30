"""Rehearsals of `ec10_4_batch.encode_4chip` end to end at toy size on
JAX-on-CPU (jobs of two toy volumes each through the admin, the
worker's batch path over its mesh, distribution and mount), and the
same run with the timed path broken underneath, on a volume that is
not the first of its job, which has to come out as not correct."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import faults, run

CELL = "ec10_4_batch.encode_4chip"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("BENCH_RUN", None)
HOST_ONLY = {"job_encode_s.batch", "job_distribute_s.batch",
             "push_phase_GBps.batch", "push_receiver_cpu_share.batch",
             "compiles_in_window.batch", "job_pull_s.batch"}
JOB = re.compile(r"  job (\w+) vol (\d+),(\d+): ([\d.]+)s ok=True "
                 r"pull=[\d.]+ encode=[\d.]+ distribute=[\d.]+ "
                 r"finish=[\d.]+: batch of 2 volumes")


def rehearse(trace, seed):
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace),
         "--rehearse"], cwd=run.REPO, env=ENV,
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


def test_the_batch_cell_rehearses_in_jobs_of_its_group():
    line, p = rehearse(0, 2147484201)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0, \
        f"{line['compared']}\n{p.stderr[-3000:]}"
    assert set(line["metrics"]) == {"rehearsal.ec_GBps",
                                    "rehearsal.setup_s"}
    assert line["metrics"]["rehearsal.ec_GBps"]["value"] > 0
    assert set(line["compared"]) == {
        "jobs_failed", "chain_dry_s", "shard_placement_faults",
        "sources_left", "parity_mismatch_bytes", "needles_wrong"}
    assert all(c["value"] == c["limit"] == 0
               for c in line["compared"].values())
    # the set-up's job is a whole group, and so is each of the window's
    assert re.search(r"set-up job on volume \d+,\d+: done", p.stdout)
    jobs = JOB.findall(p.stdout)
    assert len(jobs) == line["attempted"] >= 1, p.stdout[-3000:]
    assert all(int(b) == int(a) + 1 for _id, a, b, _s in jobs)
    said = re.search(r"window's volumes: wanted (\d+), budget (\d+), "
                     r"loaded (\d+), started (\d+); closed by", p.stdout)
    wanted, budget, loaded, started = (int(g) for g in said.groups())
    assert wanted % 2 == budget % 2 == loaded % 2 == 0
    assert started == 2 * len(jobs)
    assert "2 volumes a job, the configuration's batch_volumes" in p.stdout
    assert "A FAULT" not in p.stdout


def test_the_traced_rehearsal_reads_what_needs_no_device():
    line, p = rehearse(1, 3147484202)
    assert line["correct"] is True, line["compared"]
    got = {n[len("rehearsal."):]: v["value"]
           for n, v in line["metrics"].items()}
    assert len(got) == len(line["metrics"])       # each under `rehearsal.`
    assert set(got) == HOST_ONLY                  # and no device metric
    assert "busy_s" not in line["device"]
    assert got["job_encode_s.batch"] > 0 and got["job_pull_s.batch"] > 0
    assert got["job_distribute_s.batch"] > 0
    assert got["compiles_in_window.batch"] == 0   # the set-up's shape
    # one `ec.encode` a job, one `ec.distribute` and `ec.pull` a volume
    assert re.search(r"'ec\.distribute': 2, 'ec\.encode': 1,", p.stdout)
    assert "'ec.pull': 2," in p.stdout


@pytest.mark.parametrize("fault,number", [
    ("flip_parity_byte", "parity_mismatch_bytes"),
    ("lose_shard", "shard_placement_faults")])
def test_a_fault_in_a_groups_later_volume_is_not_correct(
        fault, number, capfd, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    seen = {}
    hooks = faults.FAULTS[fault]()
    plant = hooks.before_verify

    def before_verify(cluster, state):
        seen["groups"] = [[v["vid"] for v in j["vols"]]
                          for j in state["jobs"]]
        plant(cluster, state)
    hooks.before_verify = before_verify
    code = run.main(["--workload", CELL, "--seed", "78", "--seconds", "2",
                     "--trace", "0", "--rehearse"], hooks)
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"][number]["value"] >= 1
    # nothing else reads a fault (a volume short of a shard file cannot
    # be compared with the reference and says so with -1)
    others = {k: c["value"] for k, c in line["compared"].items()
              if k != number and c["value"] != 0}
    assert others == ({"parity_mismatch_bytes": -1}
                      if fault == "lose_shard" else {}), others
    assert all(len(g) == 2 for g in seen["groups"])
