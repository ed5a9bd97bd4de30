"""`push_dat_share` (PR 35): of the bytes the window's `ec.push` spans
carry, the share whose `source` is "dat": sent out of the `.dat` the
job pulled, where the worker writes no data shard file."""

import json
import os

import pytest

from bench_tree import spec_tree  # noqa: F401 — a fixture
from benchmark import job_trace, run

BENCH = os.path.join(run.REPO, "benchmark")
CELL = "ec10_4_vol1g.encode"
SHARD, SIDE = 106_954_752, 20_340       # a 1016 MiB volume's, on the v5e


def reader():
    return run.metric_reader(BENCH, "push_dat_share")


def job_of(sources: "list[str | None]") -> "list[dict]":
    """One job's `ec.push` spans: a shard each, then `.ecx` three
    times; a source of None is a push of the program before PR 35."""
    spans = []
    for i, source in enumerate(sources + ["file"] * 3):
        attrs = {"ext": f".ec{i:02d}" if i < len(sources) else ".ecx",
                 "bytes": SHARD if i < len(sources) else SIDE}
        if source is not None:
            attrs.update(source=source, ranges=102 if source == "dat" else 1)
        spans.append({"spanId": f"p{i}", "name": "ec.push",
                      "role": "worker", "start": float(i),
                      "durationMs": 100.0, "attrs": attrs})
    return spans


def ctx_of(jobs: int) -> dict:
    return {"jobs": [{"id": f"j{i}", "ok": True} for i in range(jobs)]}


@pytest.mark.parametrize("sources,want", [
    (["dat"] * 10 + ["file"] * 4, 10 * SHARD / (14 * SHARD + 3 * SIDE)),
    (["dat"] * 6 + ["file"] * 3, 6 * SHARD / (9 * SHARD + 3 * SIDE)),
    (["file"] * 14, 0.0),           # a batch job: files, all of them
    ([None] * 14, 0.0),             # no push says: the parent reads 0
])
def test_the_share_is_the_dat_pushes_bytes_over_all_pushes_bytes(
        sources, want):
    ctx = ctx_of(2)
    job_trace.preload(ctx, [job_of(sources), job_of(sources)])
    got = reader()(ctx)
    assert got == pytest.approx(want) and isinstance(got, float)
    if want:
        assert got == pytest.approx(len([s for s in sources if s == "dat"])
                                    / len(sources), abs=1e-3)


def test_no_push_found_is_nothing_not_nought():
    ctx = ctx_of(1)
    job_trace.preload(ctx, [[{"spanId": "e", "name": "ec.encode",
                              "role": "worker", "start": 0.0,
                              "durationMs": 900.0}]])
    assert reader()(ctx) is None
    job_trace.preload(ctx, [[]])       # the ring had turned over
    assert reader()(ctx) is None


def test_the_recording_of_an_older_program_reads_nought():
    with open(os.path.join(BENCH, "testdata", "job_traces_v5e.json")) as f:
        rec = json.load(f)
    job_trace.preload(rec["ctx"], rec["traces"])
    assert reader()(rec["ctx"]) == 0.0


def test_the_entry_is_the_idle_clusters_cells(spec_tree):
    spec = spec_tree.spec
    (m,) = [e for e in spec["per_layer"] if e["name"] == "push_dat_share"]
    assert m == {"name": "push_dat_share", "unit": "share",
                 "better": "higher", "source": "program_span",
                 "layer": "maintenance plane", "moves": "ec_GBps",
                 "workloads": [CELL]}
    assert m in run.metrics_of(spec, "per_layer", CELL)
    assert os.path.exists(os.path.join(spec_tree.bench, "metrics",
                                       "push_dat_share.py"))
