"""Group commit: one durability barrier amortized across concurrent
writers (the classic DB write-ahead-log trick, and the exact host-side
overhead arXiv:1709.05365 measures dominating online-EC stores).

The write path pays three per-request durability barriers — the filer
store's transaction commit, the metadata log's segment flush, and the
volume's .dat+.idx flush (plus `os.fsync` on the -fsync tier).  Each
is correct but serial: N concurrent writers pay N barriers for bytes
that one barrier would have covered.  `CommitBarrier` turns each site
into leader/follower group commit:

* a writer finishes its (cheap, buffered) mutation, then calls
  `commit()`;
* the first writer to arrive becomes the LEADER of the open batch;
  later arrivals join the batch as followers and block;
* the leader waits for the previous batch's flush to finish (batches
  are strictly serialized — this wait IS the gather window: while
  batch N flushes, batch N+1's members accumulate, so batch size
  self-clocks to barrier latency), closes its batch, runs the flush
  callback ONCE, and wakes every member;
* every member returns only after a flush that started after its
  mutation was buffered — ack semantics are byte-for-byte the same as
  flush-per-write, the barrier is just shared.

A single in-flight writer passes straight through: it becomes leader
of a batch of one and flushes immediately, so p50 at concurrency=1 is
the seed's p50 (no gather sleep on an idle site).  An optional linger
(`SEAWEEDFS_TPU_GROUP_COMMIT_MAX_WAIT_US`, default 0) lets a leader
that already has company hold the batch open briefly for stragglers —
useful only when the barrier is expensive relative to arrival spacing
(the -fsync tier); the self-clocking serialization needs no linger.

A flush failure (ENOSPC, a closed handle) propagates to EVERY member
of the failed batch — no writer is acked by a barrier that did not
reach the kernel.

Knobs (env):
  SEAWEEDFS_TPU_GROUP_COMMIT              "0" disables the layer:
                                          commit() == flush() (seed
                                          per-write behavior)
  SEAWEEDFS_TPU_GROUP_COMMIT_MAX_WAIT_US  leader linger for a batch
                                          that already has >= 2
                                          members (0)
  SEAWEEDFS_TPU_GROUP_COMMIT_MAX_BATCH    linger stops once the batch
                                          reaches this size (64)

Observability: every flushed batch lands
`group_commit_batch_size{site}` (histogram — mean batch = sum/count)
and every writer's barrier wait lands
`group_commit_wait_seconds{site}` in stats.PROCESS, rendered by
`cluster.top`.
"""

from __future__ import annotations

import os
import threading
import time


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def enabled() -> bool:
    """SEAWEEDFS_TPU_GROUP_COMMIT=0 reverts every site to per-write
    flushes (the bench A/B's off arm)."""
    return os.environ.get("SEAWEEDFS_TPU_GROUP_COMMIT", "1") != "0"


def max_wait_s() -> float:
    """Leader linger window in seconds (from the _MAX_WAIT_US knob)."""
    return max(0, _env_int(
        "SEAWEEDFS_TPU_GROUP_COMMIT_MAX_WAIT_US", 0)) / 1e6


def max_batch() -> int:
    return max(1, _env_int("SEAWEEDFS_TPU_GROUP_COMMIT_MAX_BATCH", 64))


def _metrics():
    from .. import stats
    return stats.PROCESS


class _Batch:
    """One commit window: members joined, a leader claimed, one flush
    verdict shared by all."""

    __slots__ = ("members", "claimed", "done", "error")

    def __init__(self):
        self.members = 0
        self.claimed = False
        self.done = threading.Event()
        self.error: "BaseException | None" = None


class CommitBarrier:
    """Leader/follower group commit around one flush callable.

    `flush` must make EVERYTHING buffered at its call time durable
    (to the OS page cache, or the platter on an fsync tier) — e.g.
    `file.flush()`, `conn.commit()`.  It is only ever called by one
    thread at a time (batches are serialized on an internal lock), and
    it may take whatever site lock it needs — the designated helper is
    where flush-under-lock is allowed (SWFS012)."""

    def __init__(self, flush, site: str = ""):
        self._flush = flush
        self.site = site
        self._lock = threading.Lock()       # guards _batch
        self._flush_lock = threading.Lock()  # serializes batch flushes
        self._batch = _Batch()
        # cumulative counters for cheap snapshots (tests, /debug)
        self.flushes = 0
        self.committed = 0
        # histogram observers, resolved lazily on first use (stats
        # imports util.* — resolving here would cycle at import time)
        self._obs_wait = None
        self._obs_flush = None

    # -- the one entry point ----------------------------------------------

    def commit(self) -> int:
        """Block until a flush that STARTED after this call covers the
        caller's buffered work.  Returns the batch size when this
        caller led the flush, 0 when it rode another leader's barrier.
        Raises the flush's exception (shared by the whole batch)."""
        if not enabled():
            # the kill switch restores per-write barriers, but the
            # flush callable's single-caller contract still holds —
            # sites like MetaLog._group_commit_drain mutate handle
            # state that concurrent unserialized flushes would race
            with self._flush_lock:
                self._flush()
            return 1
        t0 = time.perf_counter()
        with self._lock:
            batch = self._batch
            batch.members += 1
            lead = not batch.claimed
            if lead:
                batch.claimed = True
        if not lead:
            batch.done.wait()
            self._note_wait(time.perf_counter() - t0)
            if batch.error is not None:
                raise batch.error
            return 0

        # leader: wait out the previous batch's flush — members pile
        # into this batch meanwhile (the self-clocking gather window)
        with self._flush_lock:
            linger = max_wait_s()
            if linger > 0:
                self._linger(batch, linger)
            with self._lock:
                # close the window: arrivals from here on buffer ahead
                # of our flush (still covered — flush-after-buffer is
                # the only ordering that matters) but wait for the
                # NEXT barrier, whose flush also starts after their
                # mutation.  Durability is never early-acked.
                self._batch = _Batch()
                n = batch.members
            try:
                self._flush()
            except BaseException as e:
                batch.error = e
                raise
            finally:
                batch.done.set()
                self._note_flush(n, time.perf_counter() - t0)
        return n

    def sync(self) -> None:
        """Force a barrier now (readers that must see persisted state:
        metalog disk replay, close paths).  Equivalent to an empty
        member's commit()."""
        self.commit()

    # -- linger (optional gather beyond the serialization window) ---------

    def _linger(self, batch: _Batch, seconds: float) -> None:
        """Hold a batch that already has company open for stragglers.
        A batch of one never lingers — single-writer p50 must not pay
        a gather sleep for followers that are not coming."""
        deadline = time.perf_counter() + seconds
        cap = max_batch()
        while True:
            with self._lock:
                n = batch.members
            if n <= 1 or n >= cap:
                return
            left = deadline - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.0002))

    # -- telemetry --------------------------------------------------------

    def _note_wait(self, seconds: float) -> None:
        # observers resolved once per site (stats.Metrics.observer,
        # ROADMAP 1d): every barrier member pays this on its ack path
        obs = self._obs_wait
        if obs is None:
            from ..stats import GROUP_COMMIT_WAIT_BUCKETS
            obs = self._obs_wait = _metrics().observer(
                "group_commit_wait_seconds",
                buckets=GROUP_COMMIT_WAIT_BUCKETS,
                help_text="time a writer waited on the shared "
                          "durability barrier", site=self.site or "?")
        obs(seconds)

    def _note_flush(self, n: int, leader_seconds: float) -> None:
        self.flushes += 1
        self.committed += n
        obs = self._obs_flush
        if obs is None:
            from ..stats import (GROUP_COMMIT_BATCH_BUCKETS,
                                 GROUP_COMMIT_WAIT_BUCKETS)
            m = _metrics()
            obs = self._obs_flush = (
                m.observer(
                    "group_commit_batch_size",
                    buckets=GROUP_COMMIT_BATCH_BUCKETS,
                    help_text="writers covered per shared durability "
                              "barrier (mean batch = sum/count)",
                    site=self.site or "?"),
                m.observer(
                    "group_commit_wait_seconds",
                    buckets=GROUP_COMMIT_WAIT_BUCKETS,
                    site=self.site or "?"))
        obs[0](float(n))
        obs[1](leader_seconds)
