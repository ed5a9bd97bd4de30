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


@pytest.mark.parametrize("wanted,n_budget,loaded,dry,closed", [
    (2, 19, 2, 22.0, "seconds"),    # loaded what it asked for, ran out
    (8, 2, 2, 0.0, "budget"),       # cut to the budget, a job on each
    (8, 3, 2, 22.0, "seconds"),     # cut, then volumes taken away
    (2, 2, 2, 22.0, "seconds"),     # at the budget, not cut by it
])
def test_volumes_running_out_is_counted_and_the_rate_stays_whole(
        wanted, n_budget, loaded, dry, closed):
    """Seconds with no job are a fault unless the memory budget cut
    the volumes and a job started on every one it left."""
    jobs, ran_out, _ = chain([4.0] * loaded, 30.0)
    assert ran_out == 22.0
    assert run.close_of(wanted, n_budget, len(jobs), ran_out) == \
        (closed, dry)
    assert run.job_rate_GBps(jobs) == pytest.approx(2 / 8.0)


def test_a_window_the_seconds_close_is_never_closed_by_the_budget():
    jobs, ran_out, _ = chain([4.0] * 8, 30.0)     # cut to 8 of 20 wanted
    assert ran_out == 0 and len(jobs) == 8        # the 8th ends at 32 s
    assert run.close_of(20, 8, len(jobs), ran_out) == ("seconds", 0)


V1016 = 1016 * (1 << 20) + 8 + 1016 * 40      # a .dat of 1016 needles
HOST = 45 * 2**30                             # the chip machine's memory
RS10_4 = {"data_shards": 10, "parity_shards": 4}
RS6_3 = {"data_shards": 6, "parity_shards": 3}


@pytest.mark.parametrize("seconds,setup_job,budget,volumes,closed", [
    (30.0, 12.4, None, 3, "seconds"), (50.0, 12.4, None, 5, "seconds"),
    (30.0, 15.3, None, 3, "seconds"), (30.0, 4.0, None, 9, "seconds"),
    (2.0, 40.0, None, 1, "seconds"),
    (50.0, 4.0, 19, 15, "seconds"),      # under the budget: today's count
    (80.0, 4.0, 19, 19, "budget"),       # wants 24: the budget's 19
    (50.0, 1.0, 19, 19, "budget"),       # a job four times as fast
])
def test_volumes_loaded_follow_the_set_ups_own_job(seconds, setup_job,
                                                   budget, volumes, closed):
    """Enough for jobs as short as 0.85 of the set-up's, and no more;
    never more than the budget holds, and then the chain ends early,
    no second of it counted dry, the rate over whole jobs."""
    wanted = run.volumes_for(seconds, setup_job, 0.85)
    n = min(wanted, budget or wanted)
    assert n == volumes
    jobs, ran_out, _ = chain([setup_job * 0.86] * n, seconds)
    by, dry = run.close_of(wanted, budget or wanted, len(jobs), ran_out)
    assert (by, dry) == (closed, 0)
    if closed == "budget":
        assert ran_out > 0 and len(jobs) == budget
        assert run.job_rate_GBps(jobs) == pytest.approx(
            1 / (setup_job * 0.86))


@pytest.mark.parametrize("cfg,n,volumes_worth", [
    (RS10_4, 1, 4.8), (RS10_4, 18, 28.6), (RS10_4, 19, 30.0),
    (RS6_3, 1, 5.0), (RS6_3, 16, 27.5), (RS10_4, 0, 0.0)])
def test_a_set_of_volumes_needs_its_shards_and_one_job_in_flight(
        cfg, n, volumes_worth):
    """n - 1 at rest as (k+r)/k of a volume, the last in flight as its
    source, the worker's copy and two sets of shard files."""
    assert run.set_bytes(cfg, V1016, n) == pytest.approx(
        volumes_worth * V1016, abs=1)


@pytest.mark.parametrize("cfg,resident,n_budget", [
    (RS10_4, 0, 19),                  # 31.96 GB of the 32.21
    (RS10_4, int(1.4 * V1016), 18),   # the set-up's own volume at rest
    (RS10_4, int(1.4 * V1016) + 3 * (30 << 20), 18),   # and the read set
    (RS6_3, int(1.5 * V1016) + 3 * (30 << 20), 16),
    (RS10_4, int(HOST * run.MEMORY_SHARE), 0)])
def test_the_budget_is_two_thirds_of_the_machine_and_holds_so_many(
        cfg, resident, n_budget):
    room = int(HOST * run.MEMORY_SHARE) - resident
    n = run.volumes_within(cfg, V1016, room)
    assert n == n_budget
    assert run.set_bytes(cfg, V1016, n) <= max(room, 0)
    assert run.set_bytes(cfg, V1016, n + 1) > room


def test_too_little_memory_is_a_failure_that_names_both_numbers():
    need = run.set_bytes(RS10_4, V1016, 19)
    run.check_room("available memory", 19, need, need)
    with pytest.raises(run.BenchFailure) as e:
        run.check_room("available memory", 19, need, 8 * 10**9)
    assert str(need) in str(e.value) and "8000000000" in str(e.value)
    assert "19 job volumes" in str(e.value) and "31.96 GB" in str(e.value)


def test_reads_beside_a_window_closed_early_leave_out_what_came_after():
    import numpy as np
    from benchmark import load
    part = {"sent": np.arange(100.0), "latency": np.full(100, 0.05),
            "status": np.zeros(100, dtype=np.int64),
            "late": np.full(100, 0.001), "timeout": 30.0}
    part["latency"][60:] = 0.5            # slower once the chain ended
    part["status"][99] = load.FAILED      # and one failed there
    whole = load.summarize([part], 0.0, 100.0)
    cut = load.summarize([part], 0.0, 59.5)
    assert whole["read_p50_ms"] == cut["read_p50_ms"] == pytest.approx(50.0)
    assert whole["read_p99_ms"] == pytest.approx(30000.0)
    assert cut["read_p99_ms"] == pytest.approx(50.0)
    assert cut["read_rps"] == pytest.approx(60 / 59.5)
    assert cut["requests_in_window"] == 60
    # the guarantees go on counting every request
    assert cut["requests"] == 100 and cut["failed"] == 1


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
