"""The job window's rule on fake job times: jobs are whole, none is
submitted after the window's seconds, the one in flight ends, and the
rate is all finished bytes over first submit to last finish; a burst is
a stated count of them in a window that stays open; and the memory
budget that bounds the window's volumes."""

import os

import pytest

from benchmark import run

GB = 10**9
BENCH = os.path.join(run.REPO, "benchmark")


class FakeCluster:
    """A clock that only the jobs move."""

    def __init__(self, durations, fail=()):
        self.t = 1000.0
        self.durations = list(durations)
        self.fail = set(fail)
        self.submitted = []

    def now(self):
        return self.t

    def submit(self, group):
        self.submitted.append([v["vid"] for v in group])
        return f"job{group[0]['vid']}"

    def wait(self, job_id):
        i = len(self.submitted) - 1
        self.t += self.durations[i]
        return {"status": "failed" if i in self.fail else "done"}


def chain(durations, seconds, fail=(), volumes=None, per_job=1):
    fake = FakeCluster(durations, fail)
    vols = [{"vid": i, "bytes": GB}
            for i in range((volumes or len(durations)) * per_job)]
    groups = [vols[i:i + per_job] for i in range(0, len(vols), per_job)]
    jobs, dry = run.run_chain(groups, seconds, fake.t, fake.submit,
                              fake.wait, now=fake.now)
    return jobs, dry, fake


def times(jobs, key):
    return [round(j[key] - 1000, 6) for j in jobs]


# name: (durations, seconds, the burst's count, volumes loaded,
#        jobs started, submits, closed by, chain_dry_s)
BURSTS = {
    "the whole burst inside the window: the rest of it is no fault": (
        [3.0] * 5, 20.0, 5, 5, 5, [0, 3, 6, 9, 12], "seconds", 0),
    "a faster program ends the burst sooner, the window stays open": (
        [1.0] * 5, 20.0, 5, 5, 5, [0, 1, 2, 3, 4], "seconds", 0),
    "the last of the burst in flight at the window's seconds ends": (
        [4.5] * 5, 20.0, 5, 5, 5, [0, 4.5, 9, 13.5, 18], "seconds", 0),
    "a program too slow for the burst starts fewer, none after seconds": (
        [6.0] * 5, 20.0, 5, 5, 4, [0, 6, 12, 18], "seconds", 0),
    "one long job swallows the window": (
        [3.0, 30.0, 3.0], 20.0, 3, 3, 2, [0, 3], "seconds", 0),
    "volumes taken away: the burst was not whole, its dry seconds count": (
        [3.0] * 2, 20.0, 5, 2, 2, [0, 3], "seconds", 14.0),
    "a burst of one": ([3.0], 20.0, 1, 1, 1, [0], "seconds", 0),
}


@pytest.mark.parametrize("case", sorted(BURSTS))
def test_a_burst_is_a_stated_count_and_the_window_stays_open(case):
    durations, seconds, count, loaded, started, submit, closed, dry = \
        BURSTS[case]
    jobs, ran_out, fake = chain(durations, seconds, volumes=loaded)
    assert len(jobs) == started and times(jobs, "submit") == submit
    # one job at a time, back to back
    assert all(b["submit"] == a["finish"] for a, b in zip(jobs, jobs[1:]))
    assert all(j["submit"] < 1000 + seconds for j in jobs)
    # the budget held the burst (set-up fails otherwise): never its close
    assert run.close_of(count, count + 1, len(jobs), ran_out,
                        burst=True) == (closed, dry)
    # the same chain without a stated count is held to fill its window
    assert run.close_of(count, count + 1, len(jobs), ran_out) == \
        ("seconds", ran_out)


@pytest.mark.parametrize("durations,seconds", [
    ([5.0, 9.0, 7.0, 11.0, 6.0], 30.0), ([29.0, 20.0, 5.0], 30.0),
    ([6.0, 6.0, 60.0, 6.0], 30.0), ([4.0] * 2, 30.0), ([4.0] * 8, 30.0)])
def test_jobs_run_back_to_back_job_for_job(durations, seconds):
    """Each job is submitted the moment its predecessor ends, the first
    at the opening, none once the window's seconds have passed."""
    jobs, dry, fake = chain(durations, seconds)
    t, want = 0.0, []
    for d in durations:
        if t >= seconds:
            break
        want.append((t, t + d))
        t += d
    assert [(j["submit"] - 1000, j["finish"] - 1000) for j in jobs] == want
    assert dry == max(0.0, seconds - t) if len(want) == len(durations) \
        else dry == 0


@pytest.mark.parametrize("jobs,window,share", [
    ([(0, 3), (4, 7), (8, 11)], (0, 12), 9 / 12),
    ([(0, 5), (5, 10)], (0, 10), 1.0),
    ([(0, 3), (8, 13)], (0, 10), 5 / 10),      # the last cut to the window
    ([], (0, 10), None)])
def test_bg_busy_share_is_the_seconds_with_a_job_in_flight(jobs, window,
                                                           share):
    ctx = {"jobs": [{"submit": a, "finish": b} for a, b in jobs],
           "window": {"open": window[0], "end": window[1], "close": 13}}
    got = run.metric_reader(BENCH, "bg_busy_share")(ctx)
    assert got == (None if share is None else pytest.approx(share))


def test_a_job_of_several_volumes_is_one_submit_and_counts_them_all():
    jobs, dry, fake = chain([4.0] * 3, 30.0, per_job=2)
    assert fake.submitted == [[0, 1], [2, 3], [4, 5]]
    assert [j["vids"] for j in jobs] == fake.submitted
    assert [j["bytes"] for j in jobs] == [2 * GB] * 3 and dry == 18.0
    assert run.job_rate_GBps(jobs) == pytest.approx(6 / 12.0)


@pytest.mark.parametrize("jobs,cfg,count", [
    ({"order": "back_to_back", "job_seconds_margin": 0.6}, {}, None),
    ({"order": "back_to_back", "job_seconds_margin": 0.6},
     {"encode_burst_volumes": 14}, None),     # not named: not a burst
    ({"count_from": "encode_burst_volumes"}, {"encode_burst_volumes": 14},
     14),
    ({"count_from": "tick"}, {"tick": 1, "encode_burst_volumes": 14}, 1),
    ({"count_from": "encode_burst_volumes"}, {}, "fails"),
    ({"count_from": "encode_burst_volumes"}, {"encode_burst_volumes": 0},
     "fails"),
    ({"count_from": "encode_burst_volumes"}, {"encode_burst_volumes": 2.5},
     "fails"),
    ({"count_from": "encode_burst_volumes"},
     {"encode_burst_volumes": "many"}, "fails")])
def test_a_bursts_count_is_the_configurations_under_the_key_named(
        jobs, cfg, count):
    if count == "fails":
        with pytest.raises(run.BenchFailure, match="not a count of jobs"):
            run.burst_of(cfg, jobs)
    else:
        assert run.burst_of(cfg, jobs) == count


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
    (RS10_4, 0, 16),                  # 27.51 GB of the 28.63
    (RS10_4, int(1.4 * V1016), 15),   # the set-up's own volume at rest
    (RS10_4, int(1.4 * V1016) + 3 * (30 << 20), 15),   # and the read set
    (RS6_3, int(1.5 * V1016) + 3 * (30 << 20), 14),    # the burst's 14
    (RS10_4, run.memory_budget(HOST), 0)])
def test_the_budget_is_two_thirds_of_what_the_machine_grants(
        cfg, resident, n_budget):
    """The chip host says 45.0 GiB and is held to 40 from outside."""
    room = run.memory_budget(HOST) - resident
    n = run.volumes_within(cfg, V1016, room)
    assert n == n_budget
    assert run.set_bytes(cfg, V1016, n) <= max(room, 0)
    assert run.set_bytes(cfg, V1016, n + 1) > room


LIMIT = run.MACHINE_LIMIT_BYTES


@pytest.mark.parametrize("total,budget", [
    (48 * 2**30, LIMIT * 2 // 3), (HOST, LIMIT * 2 // 3),
    (LIMIT + 1, LIMIT * 2 // 3), (LIMIT, LIMIT * 2 // 3),
    (LIMIT - 3, (LIMIT - 3) * 2 // 3), (32 * 2**30, 32 * 2**30 * 2 // 3),
    (450 * 10**6, 300 * 10**6)])
def test_the_machine_is_the_least_of_memtotal_and_the_stated_limit(
        total, budget):
    assert LIMIT == 40 * 2**30
    assert run.memory_budget(total) == pytest.approx(budget, abs=1)


class SizingCluster:
    """Stands where `Run.load_job_volumes` asks the cluster to load."""

    def __init__(self):
        self.asked = None

    def load_volumes(self, seed, shapes, first_index=0, group=1):
        self.asked, self.group = (len(shapes), first_index), group
        return [{"vid": i} for i in range(len(shapes))]


def sizing_run(monkeypatch, tmp_path, workload, seconds, memtotal,
               resident=int(1.6e9)):
    import argparse
    from benchmark import cluster as cl
    args = argparse.Namespace(workload=workload, seed=1, seconds=seconds,
                              trace=0, rehearse=False, held=False)
    r = run.Run(args, run.Hooks(memory_total=memtotal))
    r.root, r.cluster = str(tmp_path), SizingCluster()
    monkeypatch.setattr(cl, "memory_now", lambda: {
        "total": 10**12, "available": 10**12, "shmem": 0})
    monkeypatch.setattr(cl, "tree_bytes", lambda _root: resident)
    monkeypatch.setattr(cl, "free_bytes", lambda _root: 10**12)
    return r


@pytest.mark.parametrize("workload,memtotal,took,loaded,n_budget", [
    # `Hooks.memory_total` above, at and below the machine's limit
    ("ec10_4_vol1g.encode", HOST, 2.46, 15, 15),
    ("ec10_4_vol1g.encode", LIMIT, 2.46, 15, 15),
    ("ec10_4_vol1g.encode", 32 * 2**30, 2.46, 11, 11),
    ("ec10_4_live.encode_under_read", HOST, 3.3, 15, 15),
    ("ec10_4_live.encode_under_read", HOST, 6.0, 11, 15),   # wants fewer
    # the burst's count whatever a job takes
    ("ec6_3_serve.read_under_encode", HOST, 3.0, 14, 14),
    ("ec6_3_serve.read_under_encode", HOST, 9.0, 14, 14),
    ("ec6_3_serve.read_under_encode", LIMIT, 0.5, 14, 14),
    # whole jobs of the configuration's four volumes, whatever the
    # set-up's toy job took
    ("ec10_4_batch.encode_4chip", HOST, 1.5, 8, 8),
    ("ec10_4_batch.encode_4chip", HOST, 40.0, 8, 8),    # wants 3 jobs
    ("ec10_4_batch.encode_4chip", HOST, 90.0, 4, 8),    # wants 1
    ("ec10_4_batch.encode_4chip", 32 * 2**30, 1.5, 4, 4)])
def test_set_up_sizes_the_window_by_budget_by_job_or_by_burst(
        monkeypatch, tmp_path, capsys, workload, memtotal, took, loaded,
        n_budget):
    r = sizing_run(monkeypatch, tmp_path, workload, 50.0, memtotal)
    vols = r.load_job_volumes(took, V1016, 4)
    assert len(vols) == loaded == r.sizing["loaded"]
    assert r.sizing["n_budget"] == n_budget
    assert r.sizing["budget"] == run.memory_budget(memtotal)
    assert r.cluster.asked == (loaded, 4)
    assert r.cluster.group == r.group == (4 if "batch" in workload else 1)
    assert loaded % r.group == n_budget % r.group == 0
    assert f"budget {n_budget} (" in capsys.readouterr().out


@pytest.mark.parametrize("seconds,memtotal,fails", [
    (50.0, HOST, False), (20.0, HOST, False), (80.0, HOST, False),
    (50.0, 39 * 2**30, False),                     # 14: the budget's last
    (50.0, 38 * 2**30, True),                      # 14 stated, 13 held
    (50.0, 32 * 2**30, True), (5.0, 32 * 2**30, True)])
def test_a_burst_the_budget_cannot_hold_is_not_cut(
        monkeypatch, tmp_path, seconds, memtotal, fails):
    """The count is the configuration's, whatever the window's seconds
    and whatever a job takes."""
    r = sizing_run(monkeypatch, tmp_path, "ec6_3_serve.read_under_encode",
                   seconds, memtotal)
    assert r.burst == r.cfg["encode_burst_volumes"] == 14
    if not fails:
        assert len(r.load_job_volumes(3.0, V1016, 4)) == 14
        return
    with pytest.raises(run.BenchFailure, match="a burst is not cut"):
        r.load_job_volumes(3.0, V1016, 4)
    assert r.cluster.asked is None


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


def test_a_failure_in_set_up_is_exit_1_and_no_line(monkeypatch, capsys):
    """What `load_job_volumes` raises over the budget reaches the shell
    as exit 1 with its reason on stderr and no result line."""
    def over(_args, _hooks):
        raise run.BenchFailure("the burst is 14 job volumes and the memory "
                               "budget holds 13: a burst is not cut")
    monkeypatch.setattr(run, "run_cell", over)
    code = run.main(["--workload", "ec6_3_serve.read_under_encode", "--seed",
                     "1", "--seconds", "50", "--trace", "0"])
    got = capsys.readouterr()
    assert code == 1 and "FAILED: the burst is 14 job volumes" in got.err
    assert not [ln for ln in got.out.splitlines() if ln.startswith("{")]
