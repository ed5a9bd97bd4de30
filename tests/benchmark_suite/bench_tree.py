"""What the benchmark's tests share: a checkout's worth of benchmark
files under `tmp_path`, which a test edits and hands to a run through
`Hooks.root`, so that entries a test makes stand where BENCHMARK.json's
do and no file of the repository is touched.  (No `conftest.py`: tests
outside this directory import the one above by that name.)"""

import json
import os
import shutil

import pytest

from benchmark import run

LISTS = ("configs", "workloads", "end_to_end", "per_layer")


class BenchTree:
    def __init__(self, root):
        self.root = str(root)
        shutil.copytree(os.path.join(run.REPO, "benchmark"),
                        os.path.join(self.root, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        self.spec = run.load_spec()
        self.held = self.read("benchmark/held_cells.json")
        self.write()

    def path(self, rel):
        return os.path.join(self.root, rel)

    def read(self, rel):
        with open(self.path(rel)) as f:
            return json.load(f)

    def write(self):
        """BENCHMARK.json and held_cells.json as they stand here."""
        self.put("BENCHMARK.json", self.spec)
        self.put("benchmark/held_cells.json", self.held)

    def put(self, rel, obj):
        with open(self.path(rel), "w") as f:
            json.dump(obj, f)

    def hold(self, cell, why="held by a test"):
        """Moves a cell out of BENCHMARK.json into held_cells.json: its
        entry, its configuration where no other cell uses it, and the
        metrics that list it alone."""
        spec, held = self.spec, self.held
        entry = next(w for w in spec["workloads"] if w["name"] == cell)
        moved = {"workloads": [entry], "configs": [
            c for c in spec["configs"] if c["name"] == entry["config"]
            and not any(w["config"] == c["name"] and w is not entry
                        for w in spec["workloads"])]}
        for key in ("end_to_end", "per_layer"):
            moved[key] = [m for m in spec[key]
                          if m.get("workloads") == [cell]]
        for key in LISTS:
            spec[key] = [e for e in spec[key] if e not in moved[key]]
            held[key] = held[key] + moved[key]
        held["why_held"][cell] = why
        self.write()
        return moved


@pytest.fixture
def bench_tree(tmp_path):
    return BenchTree(tmp_path / "checkout")
