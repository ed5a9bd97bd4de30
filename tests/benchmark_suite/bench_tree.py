"""What the benchmark's tests share: a checkout's worth of benchmark
files under `tmp_path`, which a test edits and hands to a run through
`Hooks.root`, so that entries a test makes stand where BENCHMARK.json's
do and no file of the repository is touched; and the two specs every
static test of BENCHMARK.json runs on, the repository's and the
"plus one" tree's.  (No `conftest.py`: tests outside this directory
import the one above by that name.)"""

import copy
import json
import os
import shutil
import types

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


SPECS = ("repo", "plus_one")
APPENDED = {"name": "device_idle_share.rb", "unit": "share",
            "better": "lower", "source": "device_trace", "layer": "device",
            "moves": "ec_GBps"}


def plus_one(tree):
    """The tree as a PR that adds a cell leaves it: the repair cell's
    configuration, traffic, workload entry and `ec_GBps` extension
    (test_repair_rehearse.repair_cell), and a per-layer entry of its
    own appended last, an existing reader under a new suffix.  New
    files and entries alone: nothing that was there is edited.  Once
    the repository holds a repair cell, this one's names take a tag,
    so that it is still one cell more."""
    # (test_repair_rehearse imports this module: imported here, not above)
    from test_repair_rehearse import repair_cell, repair_names
    taken = {e["name"] for lists in (tree.spec, tree.held)
             for key in LISTS for e in lists[key]}
    for kind in ("configs", "traffic"):
        taken |= {os.path.splitext(n)[0] for n in os.listdir(
            tree.path(os.path.join("benchmark", kind)))}
    tag = ""
    while taken & {*repair_names(tag), APPENDED["name"] + tag}:
        tag += "_plus"
    repair_cell(tree, rotation=4, tag=tag)
    tree.spec["per_layer"].append(dict(
        APPENDED, name=APPENDED["name"] + tag,
        workloads=[repair_names(tag)[2]]))
    tree.write()
    return tree


@pytest.fixture(scope="session", params=SPECS)
def spec_tree(request, tmp_path_factory):
    """`root`, `bench` (its benchmark/) and `spec`: the repository as it
    stands, or a checkout of
    it with one cell more (`plus_one`), so that a static test of
    BENCHMARK.json that holds only for the cells there are now fails
    here and not in the PR that adds the next one."""
    root = run.REPO
    if request.param == "plus_one":
        tree = BenchTree(tmp_path_factory.mktemp("plus_one"))
        for p in tree.spec["paths"]:
            if p != "benchmark":
                shutil.copytree(os.path.join(run.REPO, p), tree.path(p),
                                ignore=shutil.ignore_patterns("__pycache__"))
        root = plus_one(tree).root
    return types.SimpleNamespace(root=root, spec=run.load_spec(root),
                                 bench=os.path.join(root, "benchmark"))


@pytest.fixture(scope="module")
def spec(spec_tree):
    return copy.deepcopy(spec_tree.spec)


@pytest.fixture(params=SPECS)
def any_bench_tree(request, tmp_path):
    """`bench_tree`, on the repository's spec or the plus-one tree's."""
    tree = BenchTree(tmp_path / "checkout")
    return plus_one(tree) if request.param == "plus_one" else tree
