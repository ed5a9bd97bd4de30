"""Rehearsals of a repair cell at toy size on JAX-on-CPU: the encode
configuration with `lost_shards` [0, 1], and a traffic of `jobs.kind`
"rebuild" over a rotation of three volumes.  The cell stands in a
checkout under `tmp_path` (`Hooks.root`): no committed file holds a
repair cell.  Repairs run back to back through the admin, the worker's
`EcRebuildHandler` and the volume role's streaming rebuild; the window's
volumes lose their shards again as each repair ends.  Broken
underneath, the same run has to come out as not correct."""

import json
import re

import pytest

from bench_tree import bench_tree  # noqa: F401 — a fixture
from benchmark import faults, run


def repair_names(tag=""):
    """The repair cell's configuration, traffic and cell names, each
    with `tag` appended where a tree already holds those without."""
    config, traffic = "ec10_4_rebuild" + tag, "rebuild" + tag
    return config, traffic, f"{config}.{traffic}"


CELL = repair_names()[2]
SIX = {"jobs_failed", "chain_dry_s", "shard_placement_faults",
       "sources_left", "parity_mismatch_bytes", "needles_wrong"}
REPAIR = re.compile(r"  job (\w+) vol (\d+): [\d.]+s ok=True locate=[\d.]+ "
                    r"rebuild=[\d.]+ balance=[\d.]+ finish=[\d.]+: volume "
                    r"\d+: rebuilt shards \[0, 1\]")


def repair_cell(tree, rotation=3, tag=""):
    """The repair cell's files and entries in the tree: the encode
    configuration with two shards lost, a traffic of repairs."""
    config, traffic, cell = repair_names(tag)
    cfg = tree.read("benchmark/configs/ec10_4_vol1g.json")
    tree.put(f"benchmark/configs/{config}.json",
             dict(cfg, name=config, lost_shards=[0, 1]))
    jobs = {"kind": "rebuild", "role": "foreground",
            "order": "back_to_back", "rotation": rotation}
    tree.put(f"benchmark/traffic/{traffic}.json", {
        "what": "ec.rebuild jobs back to back over a rotation of volumes",
        "reads": None, "jobs": jobs, "verify": {"needles_per_volume": 8},
        "rehearse": {"jobs": jobs}})
    spec = tree.spec
    spec["configs"].append(dict(spec["configs"][0], name=config,
                                file=f"benchmark/configs/{config}.json"))
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": traffic, "chips": 1,
                              "why": "repairs back to back"})
    ec = next(m for m in spec["end_to_end"] if m["name"] == "ec_GBps")
    ec["workloads"].append(cell)
    tree.write()
    return tree


def rehearse(tree, capfd, hooks=None, seed=2147484301, seconds=4):
    hooks = hooks or run.Hooks()
    hooks.root = tree.root
    code = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", "0", "--rehearse"], hooks)
    got = capfd.readouterr()
    assert code == 0, got.err[-3000:] + got.out[-3000:]
    return json.loads(got.out.strip().splitlines()[-1]), got


def test_repairs_rehearse_back_to_back_over_the_rotation(
        bench_tree, capfd, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    line, got = rehearse(repair_cell(bench_tree), capfd)
    out = got.out
    assert line["correct"] is True, f"{line['compared']}\n{out[-4000:]}"
    assert set(line["compared"]) == SIX | {"rebuilt_wrong",
                                           "rebuilt_bytes_wrong"}
    assert all(c["value"] == c["limit"] == 0
               for c in line["compared"].values())
    assert set(line["metrics"]) == {"rehearsal.ec_GBps",
                                    "rehearsal.setup_s"}
    assert line["metrics"]["rehearsal.ec_GBps"]["value"] > 0
    # the set-up encodes the warm volume, then repairs it: the job
    # whose program the window's repairs use
    assert re.search(r"set-up job on volume \d+: done in [\d.]+s "
                     r"\([\d.]+s of it compiling\): volume \d+: rebuilt "
                     r"shards \[0, 1\]", out), out[-3000:]
    assert "rotation: 3 volumes encoded and shards [0, 1] kept aside" \
        in out
    # every repair of the window rebuilt the two lost shards, round
    # robin, and one volume at least was repaired twice
    jobs = REPAIR.findall(out)
    assert len(jobs) == line["attempted"] >= 4, out[-4000:]
    vids = [int(v) for _id, v in jobs]
    assert vids[:3] == sorted(set(vids)) and len(set(vids)) == 3
    assert all(b == vids[i % 3] for i, b in enumerate(vids))
    assert "closed by seconds (4s)" in out and "A FAULT" not in out
    # each repair that ended inside the window was read back against
    # the shards as encoded before its volume lost them again
    back = re.search(r"(\d+) repairs read back before their loss, 0 "
                     r"rebuilt bytes wrong", out)
    # (all but the last, unless it too ended inside the window)
    assert back and len(jobs) - 1 <= int(back.group(1)) <= len(jobs), \
        out[-4000:]


@pytest.mark.parametrize("fault,number", [
    ("flip_rebuilt_byte", "rebuilt_bytes_wrong"),
    ("lose_one_more", "rebuilt_wrong"),
    ("starve_chain", "chain_dry_s")])
def test_a_broken_repair_is_not_correct(fault, number, bench_tree, capfd,
                                        monkeypatch):
    """The harness's look for a chip skipped (--rehearse), the rest of
    a run driven with the fault planted: a byte altered in what the
    window's second repair rebuilt, which that volume's next repair
    writes anew, so that only the read-back before each loss sees it;
    a shard more lost after the first repair, so that a later one
    rebuilds three; or a rotation of one volume, which the chain waits
    for after each repair."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    line, got = rehearse(repair_cell(bench_tree), capfd,
                         faults.FAULTS[fault](), seed=79)
    assert line["correct"] is False, got.out[-3000:]
    assert line["compared"][number]["value"] > 0
