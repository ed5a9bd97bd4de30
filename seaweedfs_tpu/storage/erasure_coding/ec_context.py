"""EC scheme context and codec backend selection.

Mirrors weed/storage/erasure_coding/ec_encoder.go:19-27 constants and
ec_context.go:11-46 ECContext.  The codec backend is chosen once per
context: "cpu" (numpy twin), "native" (C++ host engine) or "jax" (TPU
kernels) — all bit-identical to klauspost/reedsolomon.  Which process
may default to the device is decided here and nowhere else
(own_device / default_backend).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

DATA_SHARDS_COUNT = 10
PARITY_SHARDS_COUNT = 4
TOTAL_SHARDS_COUNT = DATA_SHARDS_COUNT + PARITY_SHARDS_COUNT
MAX_SHARD_COUNT = 32          # ShardBits is uint32
MIN_TOTAL_DISKS = TOTAL_SHARDS_COUNT // PARITY_SHARDS_COUNT + 1
LARGE_BLOCK_SIZE = 1024 * 1024 * 1024   # 1GB
SMALL_BLOCK_SIZE = 1024 * 1024          # 1MB

# Batch bytes per encode step (the Go path uses 256KB,
# ec_encoder.go:61-67; any batch that divides the block size yields
# byte-identical shard files: geometry is preserved either way).  The
# host codecs step by CPU_BATCH_SIZE.  A device codec steps by what one
# staging window holds (ECContext.batch_size / rows_per_launch);
# TPU_BATCH_SIZE is what parallel/ec_batch.py's own loop sends a launch.
CPU_BATCH_SIZE = 1024 * 1024
TPU_BATCH_SIZE = 64 * 1024 * 1024


def to_ext(shard_id: int) -> str:
    """Shard file extension ".ecNN" (ec_encoder.go:107 ToExt) — single
    definition; ECContext.to_ext delegates here."""
    return f".ec{shard_id:02d}"


def _cpu_engine() -> str:
    try:
        from ...ops import rs_native
        if rs_native.available():
            return "native"
    except Exception:  # noqa: SWFS004 — pragma: no cover; probing an
        pass           # optional native build must never fail open
    return "cpu"


# -- the accelerator belongs to one process -------------------------------
#
# A TPU is held by the first process that initialises a JAX backend on
# it; a second one hangs in libtpu or drops to JAX-on-CPU without
# raising.  So exactly one process per host declares ownership with
# own_device() — the `worker` command, chip_smoke.py's device child —
# and every other role (master, volume, filer and its pre-fork
# siblings, s3, admin, shell, mq, webdav ...) resolves ECContext() to
# the host engine without importing jax.

class DeviceUnavailable(RuntimeError):
    """JAX found no accelerator where one was required."""


_owner: "dict | None" = None     # own_device()'s device record
_compiles = {"requests": 0, "cacheHits": 0, "seconds": 0.0}


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: wherever
    JAX_COMPILATION_CACHE_DIR says (placed from outside; never
    overridden in code), else a FIXED `<checkout>/.jax_cache` — the
    path is part of the cache key, so a temp name, pid or timestamp
    would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compiles["cacheHits"] += 1


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["requests"] += 1
        _compiles["seconds"] += seconds


def compile_ledger() -> dict:
    """Compilations since own_device(): every distinct program
    (`requests`), how many the persistent cache served (`cacheHits`),
    how many the backend really compiled (`compiled`), and the wall
    seconds spent in either."""
    led = dict(_compiles)
    led["compiled"] = led["requests"] - led["cacheHits"]
    led["seconds"] = round(led["seconds"], 3)
    return led


def device_info() -> dict:
    """Initialise JAX and say where it runs: {"platform", "kind",
    "count"} as jax.devices() reports them.  Raises DeviceUnavailable
    when JAX resolved to CPU without being asked to (a missing or
    busy chip makes JAX fall back silently) — JAX-on-CPU happens only
    when JAX_PLATFORMS / jax_platforms names cpu, which is what
    tier-1 sets."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    asked = (jax.config.jax_platforms or "").split(",")
    if platform == "cpu" and "cpu" not in asked:
        raise DeviceUnavailable(
            "JAX found no accelerator and fell back to cpu; the device "
            "codec refuses to run there unasked (JAX_PLATFORMS=cpu asks "
            "for it; backend native|cpu is the host codec)")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def own_device() -> dict:
    """Declare THIS process the owner of the accelerator and return
    its device_info().  Called once, before the first jit: places the
    persistent compile cache (every compile is cacheable, sub-second
    ones too, so a warm process compiles nothing), starts the compile
    ledger, initialises the backend.  A failed init raises."""
    global _owner
    if _owner is None:
        import jax
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              compile_cache_dir())
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration)
        _owner = device_info()
    return _owner


def where(backend: str) -> dict:
    """What a job result says about where its codec ran."""
    if backend != "jax":
        return {"backend": backend, "platform": "host"}
    return dict(device_info(), backend=backend)


def _measure_cpu_engine_gbps(engine: str) -> float:
    """Throughput of the host codec at pipeline batch size (1MB/shard)."""
    import time

    import numpy as np
    if engine == "native":
        from ...ops.rs_native import ReedSolomonNative as RS
    else:
        from ...ops.rs_cpu import ReedSolomonCPU as RS
    codec = RS(DATA_SHARDS_COUNT, PARITY_SHARDS_COUNT)
    data = np.random.default_rng(0).integers(
        0, 256, size=(DATA_SHARDS_COUNT, CPU_BATCH_SIZE), dtype=np.uint8)
    codec.parity(data[:, :4096])  # warmup
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        codec.parity(data)
        best = min(best, time.perf_counter() - t0)
    return data.size / best / 1e9


def _measure_h2d_gbps() -> float:
    """Host->device feed rate — the e2e ceiling of the device backend
    (input bytes move host->device 1:1)."""
    import time

    import jax
    import numpy as np
    host = np.random.default_rng(1).integers(
        0, 2**32, size=(8 << 20) // 4, dtype=np.uint32)
    jax.device_put(host[:1024]).block_until_ready()  # warmup
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return host.nbytes / best / 1e9


def probe_backend() -> dict:
    """Measure the feed rates that decide the owner's default encode
    backend: host codec GB/s vs host->device GB/s.  Owner process
    only (it touches the device); a failed measurement raises.
    Returns {"cpu_engine": "native"|"cpu", "cpu_gbps": float,
    "h2d_gbps": float, "choice": str}."""
    engine = _cpu_engine()
    rec = {"cpu_engine": engine,
           "cpu_gbps": round(_measure_cpu_engine_gbps(engine), 3),
           "h2d_gbps": round(_measure_h2d_gbps(), 3)}
    rec["choice"] = "jax" if rec["h2d_gbps"] > rec["cpu_gbps"] \
        else engine
    return rec


_cached_default: str | None = None


def default_backend() -> str:
    """The engine ECContext() gets when none is named.  An explicit
    SEAWEEDFS_TPU_EC_BACKEND=jax|native|cpu wins.  A process that has
    not called own_device() gets the host engine and never imports
    jax.  The owner gets the engine that wins END-TO-END on this
    machine, not the one with the fastest kernel: a chip behind a
    host->device path slower than the host codec loses to it, so the
    two are chosen between by a once-per-process feed-rate probe."""
    global _cached_default
    env = os.environ.get("SEAWEEDFS_TPU_EC_BACKEND")
    if env in ("jax", "native", "cpu"):
        return env
    if _owner is None or _owner["platform"] == "cpu":
        return _cpu_engine()
    if _cached_default is None:
        _cached_default = probe_backend()["choice"]
    return _cached_default


@dataclass
class ECContext:
    """Carries the RS scheme for one volume's EC operations."""

    data_shards: int = DATA_SHARDS_COUNT
    parity_shards: int = PARITY_SHARDS_COUNT
    collection: str = ""
    volume_id: int = 0
    backend: str = field(default_factory=default_backend)

    @property
    def total(self) -> int:
        return self.data_shards + self.parity_shards

    def __post_init__(self):
        if not (0 < self.data_shards and
                0 < self.parity_shards and
                self.total <= MAX_SHARD_COUNT):
            raise ValueError(
                f"bad EC scheme {self.data_shards}+{self.parity_shards}")

    def to_ext(self, shard_id: int) -> str:
        return to_ext(shard_id)

    def create_codec(self):
        if self.backend == "jax":
            device_info()  # refuses a JAX that fell back to cpu
            from ...ops.rs_jax import ReedSolomonJax
            return ReedSolomonJax(self.data_shards, self.parity_shards)
        if self.backend == "native":
            from ...ops.rs_native import ReedSolomonNative
            return ReedSolomonNative(self.data_shards,
                                     self.parity_shards)
        from ...ops.rs_cpu import ReedSolomonCPU
        return ReedSolomonCPU(self.data_shards, self.parity_shards)

    def _window_per_shard(self) -> int:
        """Bytes of one shard row that fit one staging window
        (ops.staging: what is put on the device in one piece)."""
        from ...ops import staging
        return max(1, staging.WINDOW_BYTES // self.data_shards)

    def batch_size(self, block_size: int) -> int:
        """Bytes per shard of one codec step WITHIN a block.  Host
        codecs: CPU_BATCH_SIZE.  Device codec: the largest chunk that
        divides the block and fits one staging window, so a step is
        one window, put as the reader filled it."""
        if self.backend != "jax":
            return min(CPU_BATCH_SIZE, block_size)
        n = -(-block_size // self._window_per_shard())
        while block_size % n:
            n += 1
        return block_size // n

    def rows_per_launch(self, block_size: int) -> int:
        """How many independent stripe rows to stack into one codec
        launch.  Rows are independent — shard i's file is the in-order
        concatenation of every row's block i — so stacking R rows on
        the batch axis yields byte-identical output.  A device codec
        gets as many whole rows as one staging window holds (3 of the
        1MB rows for RS(10,4), 5 for RS(6,3)): the launch IS the
        window, one compiled shape per scheme whatever the volume's
        size, and never one blocking round-trip per 1MB block (the
        round-2 3,000x end-to-end collapse)."""
        if self.backend != "jax":
            return max(1, CPU_BATCH_SIZE // block_size)
        return max(1, self._window_per_shard() // block_size)

    def __str__(self) -> str:
        return f"{self.data_shards}+{self.parity_shards}"
