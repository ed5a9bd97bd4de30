"""BENCHMARK.json is well-formed, every file it names is found by name,
and a new cell, configuration or per-layer metric is files plus entries.
Each test runs on the repository's spec and on the plus-one tree's
(bench_tree.spec_tree): a test that held only for the cells there are
now would fail on the second."""

import json
import os
import re
import shutil

import pytest

from bench_tree import any_bench_tree, spec, spec_tree  # noqa: F401
from benchmark import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys(spec_tree):
    spec, root = spec_tree.spec, spec_tree.root
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    assert isinstance(spec["run_seconds"], int)
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 << 10
    for word in spec["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
        assert ".." not in word.split("/")
    for p in spec["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(root, p))


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_names_units_and_lines(spec):
    seen = {"configs": set(), "workloads": set(), "metrics": set()}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in seen["configs"]
        seen["configs"].add(c["name"])
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] not in seen["workloads"]
        seen["workloads"].add(w["name"])
        assert w["config"] in seen["configs"]
        assert w["chips"] in (1, 4) and _line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen["metrics"]
        seen["metrics"].add(m["name"])
        assert UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for w in m.get("workloads", []):
            assert w in seen["workloads"]
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert _line(m["layer"])
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    assert {c["name"] for c in spec["configs"]} == \
        {w["config"] for w in spec["workloads"]}


def test_every_cell_reports_enough(spec):
    for w in spec["workloads"]:
        e2e = {m["name"] for m in run.metrics_of(spec, "end_to_end",
                                                 w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = run.metrics_of(spec, "per_layer", w["name"])
        assert layer
        for m in layer:      # a per-layer metric's cells report what it moves
            assert m["moves"] in e2e, (m["name"], w["name"])


def test_per_layer_moves_one_metric_its_cells_report(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", cells)


def test_every_file_found_by_name(spec_tree):
    spec, root = spec_tree.spec, spec_tree.root
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    files = set()
    for c in spec["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for key in ("source", "assumed", "guarantees", "chip_map",
                    "reference", "data_shards", "parity_shards"):
            assert key in cfg, (c["name"], key)
    for w in spec["workloads"]:
        got = run.cell_files(spec, w["name"], root)
        assert got["cfg"]["name"] == w["config"]
        assert "reads" in got["traffic"] and "jobs" in got["traffic"]
    for m in spec["per_layer"]:
        assert callable(run.metric_reader(
            os.path.join(root, "benchmark"), m["name"]))
    for p in spec["paths"]:
        for d, _dirs, names in os.walk(os.path.join(root, p)):
            if "__pycache__" in d:
                continue
            for n in names:
                rel = os.path.relpath(os.path.join(d, n), root)
                assert allowed.match(rel), rel


def test_a_new_cell_config_and_metric_are_files_and_entries(tmp_path,
                                                           spec_tree):
    """Add a dummy configuration, traffic mix, cell and per-layer
    metric to a copy: only new files and new entries, and the harness
    finds each by its name."""
    spec = spec_tree.spec
    shutil.copytree(spec_tree.bench, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = json.loads(json.dumps(spec))
    cfg = json.load(open(tmp_path / "benchmark/configs/ec6_3_serve.json"))
    cfg["name"] = "dummy_cfg"
    (tmp_path / "benchmark/configs/dummy_cfg.json").write_text(
        json.dumps(cfg))
    (tmp_path / "benchmark/traffic/dummy_mix.json").write_text(json.dumps(
        {"what": "reads alone", "jobs": None, "verify": {},
         "reads": {"processes": 1, "threads_per_process": 4,
                   "keys": "uniform", "timeout_s": 30.0}}))
    (tmp_path / "benchmark/metrics/dummy_metric.py").write_text(
        "def read(ctx):\n    return ctx['reads']['late_max_ms']\n")
    new["configs"].append({"name": "dummy_cfg", "source": "none",
                           "file": "benchmark/configs/dummy_cfg.json",
                           "reduced": ["read_objects"], "why": "test"})
    new["workloads"].append({"name": "dummy_cfg.dummy_mix",
                             "config": "dummy_cfg", "traffic": "dummy_mix",
                             "chips": 1, "why": "test"})
    for m in new["end_to_end"]:        # the read metrics come with it
        if m["name"].startswith("read_"):
            m["workloads"].append("dummy_cfg.dummy_mix")
    new["per_layer"].append({
        "name": "dummy_metric", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "benchmark generator",
        "moves": "read_p99_ms", "workloads": ["dummy_cfg.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    got_spec = run.load_spec(str(tmp_path))
    got = run.cell_files(got_spec, "dummy_cfg.dummy_mix", str(tmp_path))
    assert got["cfg"]["name"] == "dummy_cfg"
    assert got["traffic"]["what"] == "reads alone"
    assert {m["name"] for m in run.metrics_of(
        got_spec, "end_to_end", "dummy_cfg.dummy_mix")} == {
        "read_rps", "read_p99_ms", "setup_s"}
    layer = run.metrics_of(got_spec, "per_layer", "dummy_cfg.dummy_mix")
    assert [m["name"] for m in layer] == ["dummy_metric"]
    read = run.metric_reader(got["bench_dir"], "dummy_metric")
    assert read({"reads": {"late_max_ms": 1.5}}) == 1.5


HELD = "ec6_3_serve.read_under_encode"


def test_held_cells_are_whole_and_stand_outside_the_benchmark(
        any_bench_tree):
    """What benchmark/held_cells.json keeps is well-formed as it
    stands, collides with nothing, and is found only when asked for:
    shown on a checkout under `tmp_path` in which a cell is held, since
    the repository's own file holds none."""
    bench_tree = any_bench_tree
    root = bench_tree.root
    whole = run.load_spec(root)
    moved = bench_tree.hold(HELD, "a test holds it")
    spec, held = run.load_spec(root), bench_tree.read(
        "benchmark/held_cells.json")
    assert [len(moved[k]) for k in ("configs", "workloads", "end_to_end")] \
        == [1, 1, 2] and len(moved["per_layer"]) >= 9
    both = run.load_spec(root, held=True)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in both[key]]
        assert len(names) == len(set(names))
        assert len(both[key]) == len(spec[key]) + len(held[key])
        assert not {e["name"] for e in held[key]} & \
            {e["name"] for e in spec[key]}
        assert sorted(names) == sorted(e["name"] for e in whole[key])
    assert set(held["why_held"]) == {w["name"] for w in held["workloads"]}
    test_names_units_and_lines(both)
    test_every_cell_reports_enough(both)
    test_per_layer_moves_one_metric_its_cells_report(both)
    for w in held["workloads"]:
        got = run.cell_files(both, w["name"], root)
        assert got["cfg"]["name"] == w["config"]
        assert got["bench_dir"] == bench_tree.path("benchmark")
        with pytest.raises(run.BenchFailure):
            run.cell_files(spec, w["name"], root)
    for m in held["per_layer"]:
        assert callable(run.metric_reader(
            bench_tree.path("benchmark"), m["name"]))


def test_the_repositorys_own_held_file_keeps_its_form_and_holds_nothing(
        spec_tree):
    spec, root = spec_tree.spec, spec_tree.root
    with open(os.path.join(root, "benchmark", "held_cells.json")) as f:
        held = json.load(f)
    assert set(held) == {"what", "why_held", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert held["why_held"] == {} and "--held" in held["what"]
    assert [held[k] for k in ("configs", "workloads", "end_to_end",
                              "per_layer")] == [[], [], [], []]
    assert run.load_spec(root, held=True) == spec


PRUNED = ["job_copy_share", "staging_pack_share.live",
          "staging_slot_wait_s.live", "staging_ready_wait_s.live"]


@pytest.mark.parametrize("name", PRUNED)
def test_an_entry_that_said_nothing_is_gone(spec_tree, name):
    """Constants since PR 29, or another entry said again (PERF.md 6,
    PR 33).  The three constants' entries in the encode cell, and
    `staging_launch_ratio`, wait for a PR that may edit
    tests/test_window_work_items.py, which indexes them (PERF.md 7)."""
    spec = spec_tree.spec
    assert name not in {m["name"] for m in spec["per_layer"]}
    stem = name[:-len(".live")] if name.endswith(".live") else name
    twin = stem in {m["name"] for m in spec["per_layer"]}
    assert os.path.exists(os.path.join(spec_tree.bench, "metrics",
                                       stem + ".py")) == twin


BATCH = "ec10_4_batch.encode_4chip"
ACCEPTED = {   # cell: (configuration, traffic, chips)
    "ec10_4_vol1g.encode": ("ec10_4_vol1g", "encode", 1),
    "ec10_4_live.encode_under_read": ("ec10_4_live", "encode_under_read", 1),
    HELD: ("ec6_3_serve", "read_under_encode", 1),
    BATCH: ("ec10_4_batch", "encode_4chip", 4),
}


def test_four_cells_three_on_one_chip_one_on_four(spec_tree):
    """The four accepted cells as they stand: three on one chip and the
    batch job on four (the one path that exists only across chips);
    `ec_GBps` on the three encode cells, the guard on the readers end
    to end in the fourth.  Not whether they are all the cells: a PR
    that adds one adds it beside them (the cap on four-chip cells is
    `test_names_units_and_lines`')."""
    spec, root = spec_tree.spec, spec_tree.root
    cells = {w["name"]: w for w in spec["workloads"]}
    assert {n: (cells[n]["config"], cells[n]["traffic"], cells[n]["chips"])
            for n in ACCEPTED} == ACCEPTED
    assert {c for c, _t, _n in ACCEPTED.values()} <= \
        {c["name"] for c in spec["configs"]}
    assert spec["run_seconds"] == 50
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert {"ec_GBps", "read_p99_ms", "read_rps", "setup_s"} <= set(e2e)
    assert e2e["ec_GBps"]["bound"] == 0.1 and \
        e2e["setup_s"]["bound"] == 0.25 and "workloads" not in e2e["setup_s"]
    assert (e2e["ec_GBps"]["better"], e2e["setup_s"]["better"]) == \
        ("higher", "lower")
    encode = set(ACCEPTED) - {HELD}
    assert encode <= set(e2e["ec_GBps"]["workloads"])
    assert HELD not in e2e["ec_GBps"]["workloads"]
    assert e2e["read_rps"]["bound"] == 0.09 and \
        e2e["read_p99_ms"]["bound"] == 0.12
    for name, better in (("read_rps", "higher"), ("read_p99_ms", "lower")):
        m = e2e[name]
        assert m["workloads"] == [HELD] and m["better"] == better
        assert m["source"] == "host_clock"
    for cell in encode:
        assert {m["name"] for m in run.metrics_of(
            spec, "end_to_end", cell)} == {"ec_GBps", "setup_s"}
    assert {m["name"] for m in run.metrics_of(spec, "end_to_end", HELD)} \
        == {"read_rps", "read_p99_ms", "setup_s"}
    # every per-layer entry says where it has something to read
    assert all(m.get("workloads") for m in spec["per_layer"])
    t = run.cell_files(spec, HELD, root)["traffic"]
    # the background is a burst of the configuration's count, which is
    # a cut of scale and listed as one; nothing in the traffic is assumed
    assert t["jobs"] == {"role": "background", "order": "back_to_back",
                         "count_from": "encode_burst_volumes"}
    assert "assumed" not in t and "period_s" not in json.dumps(t)
    cfg = run.cell_files(spec, HELD, root)["cfg"]
    assert run.burst_of(cfg, t["jobs"]) == 14
    conf = {c["name"]: c for c in spec["configs"]}["ec6_3_serve"]
    assert conf["reduced"] == cfg["reduced"] == ["read_objects",
                                                 "encode_burst_volumes"]


GUARD_LAYER = {   # name: (unit, better, source, layer, moves)
    "device_idle_share.rd": ("share", "lower", "device_trace", "device",
                             "read_p99_ms"),
    "compiles_in_window.rd": ("count", "lower", "program_counter",
                              "device selection", "read_p99_ms"),
    "bg_encode_GBps": ("GB/s", "higher", "host_clock", "maintenance plane",
                       "read_p99_ms"),
    "rd_volume_request_ms": ("ms", "lower", "program_counter",
                             "serving planes", "read_p99_ms"),
    "rd_needle_cache_hit_share": ("share", "higher", "program_counter",
                                  "serving planes", "read_p99_ms"),
    "rd_generator_late_ms": ("ms", "lower", "host_clock",
                             "benchmark generator", "read_p99_ms"),
    "bg_busy_share": ("share", "lower", "host_clock", "maintenance plane",
                      "read_p99_ms"),
    "rd_p50_ms": ("ms", "lower", "host_clock", "serving planes",
                  "read_rps"),
    "hb_errors.rd": ("count", "lower", "program_counter", "serving planes",
                     "read_p99_ms"),
    "rd_remote_interval_share.rd": ("share", "lower", "program_counter",
                                    "serving planes", "read_p99_ms"),
}


@pytest.mark.parametrize("name", sorted(GUARD_LAYER))
def test_the_guards_per_layer_entries(spec_tree, name):
    spec = spec_tree.spec
    m = {e["name"]: e for e in spec["per_layer"]}[name]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == GUARD_LAYER[name]
    assert m["workloads"] == [HELD]
    assert callable(run.metric_reader(spec_tree.bench, name))
    assert m in run.metrics_of(spec, "per_layer", HELD)
