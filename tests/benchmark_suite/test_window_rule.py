"""The job window's rule on fake job times: jobs are whole, none is
submitted after the window's seconds, the one in flight ends, and the
rate is all finished bytes over first submit to last finish."""

import pytest

from benchmark import run

GB = 10**9


class FakeCluster:
    """A clock that only the jobs move."""

    def __init__(self, durations, fail=()):
        self.t = 1000.0
        self.durations = list(durations)
        self.fail = set(fail)
        self.submitted = []

    def now(self):
        return self.t

    def submit(self, vol):
        self.submitted.append(vol["vid"])
        return f"job{vol['vid']}"

    def wait(self, job_id):
        i = len(self.submitted) - 1
        self.t += self.durations[i]
        return {"status": "failed" if i in self.fail else "done"}


def chain(durations, seconds, fail=(), volumes=None):
    fake = FakeCluster(durations, fail)
    vols = [{"vid": i, "bytes": GB}
            for i in range(volumes or len(durations))]
    jobs, dry = run.run_chain(vols, seconds, fake.t, fake.submit,
                              fake.wait, now=fake.now)
    return jobs, dry, fake


def test_unequal_jobs_rate_is_bytes_over_first_submit_to_last_finish():
    jobs, dry, _ = chain([5.0, 9.0, 7.0, 11.0, 6.0], 30.0)
    # submits at 0, 5, 14, 21; at 32 the window's 30 s have passed
    assert [j["submit"] - 1000 for j in jobs] == [0, 5, 14, 21]
    assert dry == 0
    assert run.job_rate_GBps(jobs) == pytest.approx(4 / 32.0)


def test_job_in_flight_at_the_deadline_runs_to_its_end():
    jobs, _dry, fake = chain([29.0, 20.0, 5.0], 30.0)
    assert len(jobs) == 2 and fake.t == 1049.0
    assert run.job_rate_GBps(jobs) == pytest.approx(2 / 49.0)


def test_a_stall_in_the_middle_counts_in_full():
    even, _, _ = chain([6.0] * 5, 30.0)
    stalled, _, _ = chain([6.0, 6.0, 60.0, 6.0], 30.0)
    assert run.job_rate_GBps(even) == pytest.approx(5 / 30.0)
    assert len(stalled) == 3          # the stall swallowed the window
    assert run.job_rate_GBps(stalled) == pytest.approx(3 / 72.0)


def test_failed_jobs_take_time_and_give_no_bytes():
    jobs, _, _ = chain([5.0, 5.0, 5.0], 12.0, fail={1})
    assert [j["ok"] for j in jobs] == [True, False, True]
    assert run.job_rate_GBps(jobs) == pytest.approx(2 / 15.0)


def test_volumes_running_out_is_counted_and_the_rate_stays_whole():
    jobs, dry, _ = chain([4.0, 4.0], 30.0)
    assert dry == 22.0 and run.job_rate_GBps(jobs) == pytest.approx(2 / 8.0)


@pytest.mark.parametrize("seconds,setup_job,volumes", [
    (30.0, 12.4, 3), (50.0, 12.4, 5), (30.0, 15.3, 3), (30.0, 4.0, 9),
    (2.0, 40.0, 1)])
def test_volumes_loaded_follow_the_set_ups_own_job(seconds, setup_job,
                                                   volumes):
    """Enough for jobs as short as 0.85 of the set-up's, and no more."""
    n = run.volumes_for(seconds, setup_job, 0.85)
    assert n == volumes
    jobs, dry, _ = chain([setup_job * 0.86] * n, seconds)
    assert dry == 0


def test_no_jobs_no_rate():
    assert run.job_rate_GBps([]) is None
    jobs, _, _ = chain([5.0], 10.0, fail={0})
    assert run.job_rate_GBps(jobs) is None


def test_job_phases_from_the_workers_progress_log():
    log = [["a", "start", 10.0], ["a", "marked readonly", 10.1],
           ["a", "copied volume files", 10.6],
           ["a", "encoding 512/1016 MiB", 11.5],
           ["a", "encoded 14 shards (jax on tpu)", 13.0],
           ["a", "distributed shards", 22.0], ["a", "end", 22.1],
           ["", "trace_start", 9.0], ["b", "start", 22.2]]
    ph = run.job_phases(log)
    assert ph["a"]["phases"] == {"pull": (10.1, 10.6),
                                 "encode": (10.6, 13.0),
                                 "distribute": (13.0, 22.0),
                                 "finish": (22.0, 22.1)}
    assert ph["a"]["start"] == 10.0 and ph["a"]["end"] == 22.1
    assert ph["b"]["phases"] == {} and ph["b"]["end"] is None


def test_latency_counts_failures_as_missing_any_limit():
    import numpy as np
    from benchmark import load
    part = {"sent": np.arange(100.0), "latency": np.full(100, 0.05),
            "status": np.zeros(100, dtype=np.int64),
            "late": np.full(100, 0.001), "timeout": 30.0}
    ok = load.summarize([part], 0.0, 100.0)
    assert ok["read_p99_ms"] == pytest.approx(50.0)
    assert ok["read_rps"] == pytest.approx(1.0)
    part["status"][:2] = load.FAILED           # 2 % fail fast
    bad = load.summarize([part], 0.0, 100.0)
    assert bad["read_p99_ms"] == pytest.approx(30000.0)
    assert bad["read_p50_ms"] == pytest.approx(50.0)
    assert bad["failed"] == 2 and bad["read_rps"] == pytest.approx(0.98)


def test_what_the_master_says_late_is_late_not_wrong():
    reads = iter([2, 2, 0, 0])
    assert run.settled("x", lambda: next(reads), 5.0, every=0.01) == 0
    assert run.settled("x", lambda: 3, 0.05, every=0.01) == 3
    assert run.settled("x", lambda: 0, 5.0) == 0
