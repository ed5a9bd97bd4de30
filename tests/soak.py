"""Soak/load harness: sustained mixed tenant traffic + EC churn with
latency-SLO and fairness assertions (the QoS plane's proving rig).

tests/chaos.py proves correctness under injected FAULTS; this module
proves behavior under sustained mixed LOAD — the "millions of users"
scenario from ROADMAP item 4 and the EC-maintenance-vs-foreground
contention arXiv:1709.05365 measures.  Building blocks:

* `SoakCluster` — chaos.Cluster (in-process master + N volume
  servers) plus an in-process filer: tenant traffic enters through
  the filer edge (where qos.py's admission middleware runs), EC
  encode/rebuild churns the volume servers underneath.

* `TenantTraffic` — chaos.Traffic's concurrent writer/reader shape,
  but tenant-tagged (X-Tenant) through the FILER and latency-sampled:
  every op lands in an `OpStats` (ok latencies, 503-throttled count,
  errors) so a scenario can assert p50/p99 and achieved rates per
  tenant.  503s are tallied as *throttled*, never as errors — being
  rate-limited is the QoS plane working.

* `EcChurn` — a background thread running real `ec.encode` /
  delete-shards / `ec.rebuild` rounds through the shell against
  pre-filled volumes, i.e. the background traffic the feedback
  throttle is supposed to subordinate.

* assertion helpers: `assert_rate_capped` (noisy tenant held to its
  token rate), `percentile`.

The tier-1 fast subset (tests/test_soak.py) runs seconds of this; the
`slow`-marked long run runs minutes, against a ProcCluster with the
same helpers.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from seaweedfs_tpu import operation
from seaweedfs_tpu.server.httpd import http_bytes, http_json

from chaos import Cluster  # noqa: F401  (re-exported for scenarios)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0,1]); 0.0 for no samples."""
    if not values:
        return 0.0
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(q * len(s) + 0.5) - 1))
    return s[idx]


class OpStats:
    """Latency + outcome accounting for one tenant's ops."""

    def __init__(self):
        self._lock = threading.Lock()
        self.lat_ok: list[float] = []
        self.throttled = 0
        self.retry_after_seen = 0
        self.errors: list[str] = []
        self.t0 = time.monotonic()
        self.t1 = self.t0

    def record_ok(self, seconds: float) -> None:
        with self._lock:
            self.lat_ok.append(seconds)
            self.t1 = time.monotonic()

    def record_throttled(self, retry_after: "str | None") -> None:
        with self._lock:
            self.throttled += 1
            if retry_after:
                self.retry_after_seen += 1
            self.t1 = time.monotonic()

    def record_err(self, msg: str) -> None:
        with self._lock:
            self.errors.append(msg)
            self.t1 = time.monotonic()

    @property
    def ok(self) -> int:
        with self._lock:
            return len(self.lat_ok)

    def wall(self) -> float:
        with self._lock:
            return max(self.t1 - self.t0, 1e-9)

    def ok_rate(self) -> float:
        return self.ok / self.wall()

    def p50(self) -> float:
        with self._lock:
            return percentile(self.lat_ok, 0.50)

    def p99(self) -> float:
        with self._lock:
            return percentile(self.lat_ok, 0.99)

    def summary(self) -> dict:
        with self._lock:
            return {
                "ok": len(self.lat_ok),
                "throttled": self.throttled,
                "errors": len(self.errors),
                "okPerSec": round(len(self.lat_ok) /
                                  max(self.t1 - self.t0, 1e-9), 2),
                "p50Ms": round(percentile(self.lat_ok, 0.5) * 1e3, 2),
                "p99Ms": round(percentile(self.lat_ok, 0.99) * 1e3, 2),
            }


class SoakCluster:
    """chaos.Cluster + an in-process filer edge."""

    def __init__(self, tmp_path, volumes: int = 3,
                 volume_size_limit_mb: int = 64):
        from seaweedfs_tpu.server.filer_server import FilerServer
        self.cluster = Cluster(
            tmp_path, volumes=volumes,
            volume_size_limit_mb=volume_size_limit_mb)
        self.filer = FilerServer(self.cluster.master_url).start()

    @property
    def master_url(self) -> str:
        return self.cluster.master_url

    @property
    def filer_url(self) -> str:
        return self.filer.url

    @property
    def all_urls(self) -> "list[str]":
        return self.cluster.all_urls + [self.filer.url]

    def prepare_ec_volumes(self, rounds: int,
                           blobs_per_volume: int = 10
                           ) -> "list[tuple[int, dict]]":
        """Pre-fill `rounds` distinct volumes (QUIESCENT cluster —
        concurrent traffic would spread each batch over volumes)."""
        out = []
        for i in range(rounds):
            vid, blobs = self.cluster.fill_volume(
                n=blobs_per_volume, seed=101 + i)
            out.append((vid, blobs))
        return out

    def stop(self) -> None:
        self.filer.stop()
        self.cluster.stop()


class TenantTraffic:
    """Concurrent tenant-tagged writer+reader through the filer.

    `target_rps=None` hammers as fast as the edge allows (the noisy-
    neighbor shape: the QoS token bucket, not client politeness, must
    do the capping); a number paces the offered load (well-behaved
    tenant).  Writes land under /soak/<tenant>/ and are remembered
    for byte-identity verification."""

    def __init__(self, filer_url: str, tenant: str,
                 payload: int = 1500, target_rps: "float | None" = None,
                 read_fraction: float = 0.5, seed: int = 7):
        self.filer_url = filer_url
        self.tenant = tenant
        self.payload = payload
        self.target_rps = target_rps
        self.read_fraction = read_fraction
        self.stats = OpStats()
        self.written: dict[str, bytes] = {}
        self._wlock = threading.Lock()
        self._rng = np.random.default_rng(seed)
        self._stop = threading.Event()
        self._n = 0
        self._thread = threading.Thread(target=self._loop,
                                        daemon=True)

    def start(self) -> "TenantTraffic":
        self._thread.start()
        return self

    def stop(self) -> "TenantTraffic":
        self._stop.set()
        self._thread.join(timeout=30)
        return self

    def _headers(self) -> dict:
        return {"X-Tenant": self.tenant}

    def _one_write(self) -> bool:
        data = self._rng.integers(0, 256, self.payload,
                                  dtype=np.uint8).tobytes()
        self._n += 1
        path = f"/soak/{self.tenant}/f{self._n}"
        t0 = time.perf_counter()
        try:
            st, body, h = http_bytes(
                "POST", f"{self.filer_url}{path}", data,
                headers=self._headers(), timeout=30)
        except (OSError, RuntimeError) as e:
            self.stats.record_err(f"write {path}: {e!r}")
            return False
        dt = time.perf_counter() - t0
        if st == 503:
            self.stats.record_throttled(h.get("Retry-After"))
            return True
        if st < 300:
            self.stats.record_ok(dt)
            with self._wlock:
                self.written[path] = data
        else:
            self.stats.record_err(f"write {path}: HTTP {st} "
                                  f"{body[:80]!r}")
        return False

    def _one_read(self) -> bool:
        with self._wlock:
            if not self.written:
                return False
            keys = list(self.written)
        path = keys[int(self._rng.integers(0, len(keys)))]
        t0 = time.perf_counter()
        try:
            st, body, h = http_bytes(
                "GET", f"{self.filer_url}{path}",
                headers=self._headers(), timeout=30)
        except (OSError, RuntimeError) as e:
            self.stats.record_err(f"read {path}: {e!r}")
            return False
        dt = time.perf_counter() - t0
        if st == 503:
            self.stats.record_throttled(h.get("Retry-After"))
            return True
        if st == 200:
            with self._wlock:
                want = self.written.get(path)
            if want is not None and body != want:
                self.stats.record_err(
                    f"read {path}: BYTES DIFFER "
                    f"({len(body)} vs {len(want)})")
            else:
                self.stats.record_ok(dt)
        else:
            self.stats.record_err(f"read {path}: HTTP {st}")
        return False

    def _loop(self) -> None:
        interval = (1.0 / self.target_rps) if self.target_rps else 0.0
        nxt = time.monotonic()
        while not self._stop.is_set():
            if self._rng.random() < self.read_fraction:
                throttled = self._one_read()
            else:
                throttled = self._one_write()
            if throttled:
                # an impolite-but-not-pathological client: a noisy
                # tenant keeps offering load far above its limit, yet
                # doesn't spin the CPU into a 503 storm that would
                # starve the very foreground this rig measures
                self._stop.wait(0.02)
            if interval:
                nxt += interval
                delay = nxt - time.monotonic()
                if delay > 0:
                    self._stop.wait(delay)
                else:
                    nxt = time.monotonic()   # fell behind: no burst

    def verify_all(self) -> int:
        """Every acked write reads back byte-identical (post-run, no
        rate limit pressure: tenant tag still attached, so run this
        after limits are lifted or under the tenant's budget)."""
        with self._wlock:
            items = list(self.written.items())
        for path, want in items:
            st, body, _ = http_bytes("GET",
                                     f"{self.filer_url}{path}",
                                     headers=self._headers(),
                                     timeout=30)
            assert st == 200, f"verify {path}: HTTP {st}"
            assert body == want, \
                f"acked write {path} corrupted " \
                f"({len(body)}B vs {len(want)}B)"
        return len(items)


class EcChurn:
    """Background EC maintenance load: encode -> lose shards ->
    rebuild, one pre-filled volume per round, through the real shell
    commands (so the scatter/rebuild pipelines — and their qos.ec_pace
    hooks — run exactly as production would)."""

    def __init__(self, master_url: str,
                 volumes: "list[tuple[int, dict]]",
                 loop: bool = False):
        self.master_url = master_url
        self.volumes = volumes
        self.loop = loop
        self.rounds_done = 0
        self.errors: list[str] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> "EcChurn":
        self._thread.start()
        return self

    def stop(self) -> "EcChurn":
        self._stop.set()
        self._thread.join(timeout=120)
        return self

    def join(self, timeout: float = 300) -> "EcChurn":
        self._thread.join(timeout=timeout)
        return self

    def _one_round(self, vid: int) -> None:
        from seaweedfs_tpu.shell import CommandEnv, run_command
        env = CommandEnv(self.master_url)
        env.lock()
        try:
            run_command(env, f"ec.encode -volumeId={vid}")
            # lose two shards, then rebuild them from survivors
            r = http_json(
                "GET",
                f"{self.master_url}/dir/ec_lookup?volumeId={vid}",
                timeout=30)
            locs = {loc["url"]: sorted(loc["shardIds"])
                    for loc in r.get("shardIdLocations", [])}
            victims = []
            for url, sids in sorted(locs.items()):
                if sids and len(victims) < 2:
                    victims.append((url, sids[-1]))
            for url, sid in victims:
                http_json("POST", f"{url}/admin/ec/delete_shards",
                          {"volumeId": vid, "shardIds": [sid]},
                          timeout=30)
            run_command(env, f"ec.rebuild -volumeId={vid}")
            if self.loop:
                # full maintenance cycle: decode back to a normal
                # volume so the NEXT round's encode has something to
                # encode (and the decode path soaks too)
                run_command(env, f"ec.decode -volumeId={vid}")
        finally:
            env.unlock()

    def _run(self) -> None:
        while True:
            for vid, _blobs in self.volumes:
                if self._stop.is_set():
                    return
                try:
                    self._one_round(vid)
                    self.rounds_done += 1
                except Exception as e:  # noqa: BLE001 — the scenario
                    # tallies; a churn failure must not kill the run
                    self.errors.append(f"vid {vid}: {e!r}")
            if not self.loop or self._stop.is_set():
                return

    def verify_blobs(self) -> None:
        """Byte identity through the EC read path after the churn."""
        for _vid, blobs in self.volumes:
            for fid, want in blobs.items():
                got = operation.read(self.master_url, fid)
                assert got == want, \
                    f"{fid}: EC read {len(got)}B != {len(want)}B"


# -- assertions ------------------------------------------------------------

def assert_rate_capped(stats: OpStats, rps_limit: float,
                       slack: float = 1.6) -> None:
    """The tenant's ACHIEVED ok-rate must sit at/below its token rate
    (+ burst/timing slack).  Only meaningful for a tenant that offered
    more load than its limit — assert stats.throttled > 0 first."""
    assert stats.throttled > 0, \
        "tenant was never throttled — offered load did not exceed " \
        "the limit, so the cap was not exercised"
    achieved = stats.ok_rate()
    assert achieved <= rps_limit * slack, \
        f"noisy tenant achieved {achieved:.1f} ok/s, expected " \
        f"<= {rps_limit} (+{slack}x slack) — the token bucket is " \
        f"not capping"


def arm_qos(url: str, body: dict) -> dict:
    """Push a QoS lever change over the runtime debug plane."""
    r = http_json("POST", f"{url}/debug/qos", body, timeout=10)
    assert isinstance(r, dict) and "config" in r, r
    return r
