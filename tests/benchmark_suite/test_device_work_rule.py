"""The rule that a cell's path reaches the chip, on a fake worker wire:
a set-up job or a window in which nothing was read back from the chip
fails the run at once, naming what did no work, and a traced run
whose trace holds no device operation in the window fails rather than
print a line without `busy_s`.  Off the chip (`--rehearse`) or where
nothing is counted (JAX on the CPU), nothing is checked."""

import argparse

import pytest

from benchmark import run
from benchmark.cluster import BenchFailure


def mark(reads, **more):
    return dict({"t": 0.0, "staging": {"launches": 0},
                 "compile": {"seconds": 0.0, "requests": 0},
                 "peak_bytes": {"tpu:0": 0}, "reads": reads},
                **more)


class FakeWire:
    """Answers each command with the next mark of its list."""

    def __init__(self, marks):
        self.marks = list(marks)
        self.asked = []

    def ask(self, cmd, timeout=60.0, **kw):
        self.asked.append(cmd)
        if cmd == "report":
            return dict(mark(None), log=[], spans=[])
        return self.marks.pop(0)


class FakeCluster:
    def __init__(self, marks, job_type="ec_rebuild", status="done"):
        self.wire = FakeWire(marks)
        self.ready = {}
        self.job = {"jobType": job_type, "status": status,
                    "message": "volume 7: rebuilt shards [0, 1]"}

    def wait_job(self, job_id, timeout):
        return self.job


class FakeProcs:
    def pids_mapping(self, needle):
        return {}


def bare_run(rehearse, trace=0):
    r = object.__new__(run.Run)
    r.args = argparse.Namespace(rehearse=rehearse, trace=trace, seconds=0.0)
    return r


# (values read back before, after, rehearsed, fails)
CASES = {
    "no work on the chip": (7, 7, False, True),
    "fewer than before": (7, 3, False, True),
    "work on the chip": (7, 19, False, False),
    "no count kept": (None, None, False, False),
    "a count on one side only": (7, None, False, False),
    "a rehearsal that did no work": (7, 7, True, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_rule_on_two_counts(case):
    before, after, rehearsed, fails = CASES[case]
    work = run.device_work(mark(before), mark(after))
    assert work == (None if before is None or after is None
                    else after - before)
    if fails:
        with pytest.raises(BenchFailure, match="did no work on the chip"):
            run.check_device_work("x", work, not rehearsed)
    else:
        run.check_device_work("x", work, not rehearsed)


def test_a_mark_without_the_count_is_not_counted():
    assert run.device_work({"t": 0}, mark(5)) is None
    assert run.work_said(None) == "not counted"
    assert run.work_said(0) == "+0" and run.work_said(12) == "+12"


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_set_up_job_that_did_no_work_on_the_chip_fails_and_is_named(
        case, capsys):
    before, after, rehearsed, fails = CASES[case]
    r = bare_run(rehearsed)
    r.cluster = FakeCluster([mark(before), mark(after)])
    vols = [{"vid": 7}]
    if fails:
        with pytest.raises(BenchFailure) as e:
            r.set_up_job(vols, lambda v: "job1")
        assert str(e.value) == (
            "the set-up's ec_rebuild job on volume 7 did no work on the "
            f"chip (values read back from it {after - before:+d}): this "
            "cell's path does not run on the device")
    else:
        assert r.set_up_job(vols, lambda v: "job1") >= 0.0
    # the set-up's line carries the count, after the job's own message
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("set-up job on volume 7: done"))
    assert line.endswith("rebuilt shards [0, 1]; values read back from the "
                         "chip " + run.work_said(run.device_work(
                             mark(before), mark(after))))
    assert r.cluster.wire.asked == ["mark", "mark"]


def test_a_failed_set_up_job_is_told_before_the_rule():
    r = bare_run(False)
    r.cluster = FakeCluster([mark(7), mark(7)], status="failed")
    with pytest.raises(BenchFailure, match="set-up job failed"):
        r.set_up_job([{"vid": 7}], lambda v: "job1")


def test_an_encode_of_several_volumes_is_named_by_all_of_them():
    r = bare_run(False)
    r.cluster = FakeCluster([mark(40), mark(40)], job_type="erasure_coding")
    with pytest.raises(BenchFailure, match=r"the set-up's erasure_coding job "
                       r"on volume 3,4,5,6 did no work on the chip"):
        r.set_up_job([{"vid": v} for v in (3, 4, 5, 6)], lambda v: "job1")


def window_run(marks, rehearsed, trace, tmp_path):
    """A run whose window holds no job and no reader: all it does is
    what the worker child's marks say."""
    r = bare_run(rehearsed, trace)
    r.hooks, r.root = run.Hooks(), str(tmp_path)
    r.cluster, r.procs = FakeCluster(marks), FakeProcs()
    r.reads, r.loaders, r.jobs_t, r.job_vols = None, [], None, []
    r.group, r.rotation, r.burst, r.sizing = 1, None, None, None
    r.seconds = 0.0
    r.cfg, r.traffic, r.dev, r.cell = {"volume_servers": 3}, {}, {}, \
        {"chips": 1}
    return r


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_window_that_did_no_work_on_the_chip_fails(case, trace, tmp_path,
                                                     capsys):
    before, after, rehearsed, fails = CASES[case]
    r = window_run([mark(before), mark(after)], rehearsed, trace, tmp_path)
    if fails:
        with pytest.raises(BenchFailure) as e:
            r.window()
        assert str(e.value) == (
            f"the window did no work on the chip (values read back from it "
            f"{after - before:+d}): this cell's path does not run on the "
            "device")
    else:
        r.window()
    # the marks that open and close the window, traced or not, are the
    # ones compared, and the ledgers' line carries the count
    assert r.cluster.wire.asked == (["trace_start", "trace_stop"] if trace
                                    else ["mark", "mark"]) + ["report"]
    assert r.work == run.device_work(mark(before), mark(after))
    assert any(ln.startswith("worker ledgers: ") and
               ln.endswith("values read back from the chip "
                           + run.work_said(r.work))
               for ln in capsys.readouterr().out.splitlines())


SYNC = [[1_000_000_000_000, 0]]          # host ns 1000 s = trace ns 0
# (device events, sync marks, window on the host's clock)
EMPTY_TRACES = {
    "no device plane": ({}, SYNC, (1000.0, 1010.0)),
    "a device plane with no operation": ({"/device:TPU:0": []}, SYNC,
                                         (1000.0, 1010.0)),
    "operations of no length": ({"/device:TPU:0": [
        ["fusion", 1_000_000_000, 0], ["fusion", 2_000_000_000, 0]]}, SYNC,
        (1000.0, 1010.0)),
    "operations only outside the window": ({"/device:TPU:0": [
        ["fusion", 20_000_000_000, 5_000_000]]}, SYNC, (1000.0, 1010.0)),
    "no sync mark to place them": ({"/device:TPU:0": [
        ["fusion", 1_000_000_000, 5_000_000]]}, [], (1000.0, 1010.0)),
}


def traced_run(devices, sync, window, rehearsed):
    r = bare_run(rehearsed, trace=1)
    r.mark1 = {"events": {"devices": devices, "sync": sync},
               "trace_bytes": 4096}
    r.t_open, r.t_close = window
    r.ctx, r.jobs = {"trace": None}, []
    return r


@pytest.mark.parametrize("case", sorted(EMPTY_TRACES))
def test_a_trace_with_no_device_operation_in_the_window_fails(case):
    devices, sync, window = EMPTY_TRACES[case]
    r = traced_run(devices, sync, window, rehearsed=False)
    device = {"platform": "tpu"}
    with pytest.raises(BenchFailure) as e:
        r.read_trace(device)
    n = sum(len(v) for v in devices.values())
    assert str(e.value).startswith(
        f"the trace holds no device operation in the window ({n} device "
        f"events on {sorted(devices)}, {len(sync)} sync marks)")
    assert "busy_s" not in device and r.ctx["trace"] is None


@pytest.mark.parametrize("case", sorted(EMPTY_TRACES))
def test_a_rehearsals_trace_has_no_device_time_and_does_not_fail(case):
    r = traced_run(*EMPTY_TRACES[case], rehearsed=True)
    device = {"platform": "cpu"}
    assert r.read_trace(device) is None
    assert device == {"platform": "cpu"} and r.ctx["trace"] is None


def test_a_trace_with_work_in_the_window_gives_the_device_its_times():
    # 5 ms at +1 s and 20 ms at +2 s on one chip, 10 ms on the other
    devices = {"/device:TPU:0": [["fusion", 1_000_000_000, 5_000_000],
                                 ["fusion.1", 2_000_000_000, 20_000_000]],
               "/device:TPU:1": [["fusion", 1_000_000_000, 10_000_000]]}
    r = traced_run(devices, SYNC, (1000.0, 1010.0), rehearsed=False)
    device = {"platform": "tpu"}
    got = r.read_trace(device)
    assert device["busy_s"] == pytest.approx((0.025 + 0.010) / 2)
    assert device["window_s"] == pytest.approx(10.0)
    assert r.ctx["trace"]["busy_s"] == device["busy_s"]
    assert [n for n, _s in got["device_ops"]] == ["fusion.1", "fusion"]
