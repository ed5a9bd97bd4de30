"""Native C++ components, built on demand.

The reference's native layer is Go-calling-SIMD-assembly + Rust
(SURVEY §2.6); ours is C++ compiled at first use (g++ is in the image;
pybind11 is not, so bindings go through ctypes).  The build artifact is
cached next to the sources, keyed on a hash of what went into it and of
the CPU it was built for.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gf_rs.cc")
_SO = os.path.join(_DIR, "_build", "libgf_rs.so")
_lock = threading.Lock()
_lib = None
_tried = False


def _host_cpu_flags() -> str:
    """This host's CPU feature flags: `-march=native` bakes them into
    the artefact, so they are part of what identifies it."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return ""


def _build_key(src_path: str, deps, flags: "list[str]") -> str:
    h = hashlib.sha256()
    for path in [src_path, *deps]:
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    h.update(" ".join(flags).encode())
    h.update(b"\0")
    h.update(_host_cpu_flags().encode())
    return h.hexdigest()


def _build_if_stale(src_path: str, out_path: str,
                    extra_flags: "list[str] | None" = None,
                    shared: bool = True,
                    try_march_native: bool = False,
                    deps: "list[str] | None" = None) -> "str | None":
    """Shared g++ build (one implementation for all the native
    artifacts).  The artefact is keyed on a hash of source + header
    deps + flags + this host's CPU flags, recorded beside it in
    `<out>.key`: an artefact that is stale, or was carried over from
    another machine (a `-march=native` build may not run on this
    CPU), is rebuilt, never loaded.  Per-pid scratch so concurrent
    builders never publish half-written output, atomic publish.  None
    when the toolchain is unavailable."""
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        base = ["g++", "-O2", "-std=c++17"]
        if shared:
            base += ["-shared", "-fPIC", "-pthread"]
        base += extra_flags or []
        attempts = ([["-march=native"], []] if try_march_native
                    else [[]])
        key = _build_key(src_path, deps or (),
                         base + [m for a in attempts for m in a])
        key_path = out_path + ".key"
        try:
            with open(key_path) as f:
                if f.read() == key and os.path.exists(out_path):
                    return out_path
        except OSError:
            pass
        tmp = f"{out_path}.{os.getpid()}.tmp"
        for march in attempts:
            try:
                subprocess.run(
                    base + march + [src_path, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, out_path)
                with open(tmp, "w") as f:
                    f.write(key)
                os.replace(tmp, key_path)
                return out_path
            except (OSError, subprocess.SubprocessError):
                continue
        return None
    except OSError:
        return None


def _build() -> str | None:
    return _build_if_stale(_SRC, _SO, extra_flags=["-O3"],
                           try_march_native=True)


def load() -> "ctypes.CDLL | None":
    """Build (if needed) + load the native library; None when no
    toolchain / no writable build dir / broken artifact — callers fall
    back to numpy/JAX and must never see an exception from here."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _build()
            if so is None:
                return None
            lib = ctypes.CDLL(so)
            lib.gf_matrix_apply.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_size_t, ctypes.c_int]
            lib.gf_mul_slice_acc.argtypes = [
                ctypes.c_uint8, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_size_t]
            lib.gf_native_simd.restype = ctypes.c_int
        except OSError:
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


# -- shared plane flight-record wire format (ISSUE 18) -----------------


class PlaneRecord(ctypes.Structure):
    """One per-request flight record drained from a C++ plane ring
    (layout mirrors PlaneRec in meta_plane.cc / write_plane.cc /
    read_plane.cc — all three share the 112-byte shape; only the
    stage/fallback label tables differ per plane)."""

    _fields_ = [("rid", ctypes.c_char * 40),
                ("start_unix_ns", ctypes.c_uint64),
                ("stage_ns", ctypes.c_uint64 * 4),
                ("bytes", ctypes.c_uint64),
                ("deadline_ms", ctypes.c_int64),
                ("status", ctypes.c_int32),
                ("fallback", ctypes.c_int32),
                ("flags", ctypes.c_uint32),
                ("_pad", ctypes.c_uint32)]


PLANE_RECORD_CLIENT_RID = 0x1  # rid arrived on the wire (vs minted)
# the wire rid has the plane-minted shape ("mp00c0ffee-1"): it was
# forwarded by a sibling plane's upstream hop, not set by a client —
# the drain sink treats such records as lean unless independently
# interesting (error / over the slow threshold)
PLANE_RECORD_MINTED_UPSTREAM = 0x2

_PLANE_RECORD_DTYPE = None


def plane_record_dtype():
    """Numpy structured-dtype mirror of PlaneRecord, for the
    vectorized drain path (profiling.PlaneRecordSink.feed_buffer).
    Lazy: the wire format must not force numpy at module load."""
    global _PLANE_RECORD_DTYPE
    if _PLANE_RECORD_DTYPE is None:
        import numpy as np
        dt = np.dtype([
            ("rid", "S40"), ("start_unix_ns", "<u8"),
            ("stage_ns", "<u8", (4,)), ("bytes", "<u8"),
            ("deadline_ms", "<i8"), ("status", "<i4"),
            ("fallback", "<i4"), ("flags", "<u4"),
            ("_pad", "<u4")])
        if dt.itemsize != ctypes.sizeof(PlaneRecord):
            raise AssertionError(
                f"PlaneRecord dtype drift: {dt.itemsize} != "
                f"{ctypes.sizeof(PlaneRecord)}")
        _PLANE_RECORD_DTYPE = dt
    return _PLANE_RECORD_DTYPE


def _bind_record_drain(lib: "ctypes.CDLL", prefix: str) -> None:
    """Wire the {mp,wp,rp}_drain_records / _records_dropped pair."""
    drain = getattr(lib, f"{prefix}_drain_records")
    drain.argtypes = [ctypes.c_int, ctypes.POINTER(PlaneRecord),
                      ctypes.c_int]
    drain.restype = ctypes.c_int
    dropped = getattr(lib, f"{prefix}_records_dropped")
    dropped.argtypes = [ctypes.c_int]
    dropped.restype = ctypes.c_ulonglong


def drain_plane_records(lib: "ctypes.CDLL", prefix: str, handle: int,
                        sink=None, cap: int = 512):
    """Pull one plane's flight ring dry.  With `sink`, feed each
    batch through sink.feed and return the total count (the hot
    drainer path — the buffer is reused, never retained); without,
    return copied PlaneRecord instances (tests/inspection)."""
    drain = getattr(lib, f"{prefix}_drain_records")
    buf = (PlaneRecord * cap)()
    out: "list | None" = [] if sink is None else None
    feed_buffer = getattr(sink, "feed_buffer", None)
    total = 0
    while True:
        n = drain(handle, buf, cap)
        if n > 0:
            total += n
            if sink is None:
                out.extend(PlaneRecord.from_buffer_copy(buf[i])
                           for i in range(n))
            elif feed_buffer is not None:
                # vectorized hot path: the sink consumes the raw
                # buffer in one numpy pass before the next drain
                # call reuses it
                feed_buffer(buf, n)
            else:
                sink.feed(buf[i] for i in range(n))
        if n < cap:
            return out if sink is None else total


# -- read-plane library (read_plane.cc) --------------------------------

_RP_SRC = os.path.join(_DIR, "read_plane.cc")
_RP_SO = os.path.join(_DIR, "_build", "libread_plane.so")
_rp_lib = None
_rp_tried = False


def load_read_plane() -> "ctypes.CDLL | None":
    """Build (if needed) + load the native epoll read plane; None when
    unavailable — the volume server then serves reads from Python
    only."""
    global _rp_lib, _rp_tried
    with _lock:
        if _rp_lib is not None or _rp_tried:
            return _rp_lib
        _rp_tried = True
        try:
            if _build_if_stale(_RP_SRC, _RP_SO) is None:
                return None
            lib = ctypes.CDLL(_RP_SO)
            lib.rp_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                     ctypes.POINTER(ctypes.c_int)]
            lib.rp_start.restype = ctypes.c_int
            lib.rp_stop.argtypes = [ctypes.c_int]
            lib.rp_add_volume.argtypes = [ctypes.c_int, ctypes.c_uint,
                                          ctypes.c_char_p]
            lib.rp_add_volume.restype = ctypes.c_int
            lib.rp_remove_volume.argtypes = [ctypes.c_int,
                                             ctypes.c_uint]
            lib.rp_put.argtypes = [ctypes.c_int, ctypes.c_uint,
                                   ctypes.c_ulonglong, ctypes.c_uint,
                                   ctypes.c_ulonglong, ctypes.c_uint]
            lib.rp_put.restype = ctypes.c_int
            lib.rp_del.argtypes = [ctypes.c_int, ctypes.c_uint,
                                   ctypes.c_ulonglong]
            lib.rp_served.argtypes = [ctypes.c_int]
            lib.rp_served.restype = ctypes.c_ulonglong
            _bind_record_drain(lib, "rp")
        except (OSError, subprocess.SubprocessError):
            return None
        _rp_lib = lib
        return _rp_lib


# -- write-plane library (write_plane.cc) ------------------------------

_WP_SRC = os.path.join(_DIR, "write_plane.cc")
_WP_SO = os.path.join(_DIR, "_build", "libwrite_plane.so")
_wp_lib = None
_wp_tried = False


class WpEntry(ctypes.Structure):
    """One completed native append, drained back to the Python index
    (layout mirrors write_plane.cc WpEntry)."""

    _fields_ = [("key", ctypes.c_uint64),
                ("offset", ctypes.c_uint64),
                ("append_ns", ctypes.c_uint64),
                ("vid", ctypes.c_uint32),
                ("cookie", ctypes.c_uint32),
                ("size", ctypes.c_int32),
                ("data_len", ctypes.c_uint32)]


def load_write_plane() -> "ctypes.CDLL | None":
    """Build (if needed) + load the native epoll write plane; None
    when unavailable — the volume server then serves writes from
    Python only (the graceful-degradation contract the parity tests
    pin)."""
    global _wp_lib, _wp_tried
    with _lock:
        if _wp_lib is not None or _wp_tried:
            return _wp_lib
        _wp_tried = True
        try:
            if _build_if_stale(_WP_SRC, _WP_SO) is None:
                return None
            lib = ctypes.CDLL(_WP_SO)
            lib.wp_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                     ctypes.POINTER(ctypes.c_int)]
            lib.wp_start.restype = ctypes.c_int
            lib.wp_stop.argtypes = [ctypes.c_int]
            lib.wp_add_volume.argtypes = [
                ctypes.c_int, ctypes.c_uint, ctypes.c_char_p,
                ctypes.c_ulonglong, ctypes.c_ulonglong, ctypes.c_int]
            lib.wp_add_volume.restype = ctypes.c_int
            lib.wp_mark_keys.argtypes = [
                ctypes.c_int, ctypes.c_uint,
                ctypes.POINTER(ctypes.c_ulonglong), ctypes.c_int]
            lib.wp_mark_keys.restype = ctypes.c_int
            lib.wp_arm.argtypes = [ctypes.c_int, ctypes.c_uint]
            lib.wp_arm.restype = ctypes.c_int
            lib.wp_remove_volume.argtypes = [ctypes.c_int,
                                             ctypes.c_uint]
            lib.wp_append.argtypes = [
                ctypes.c_int, ctypes.c_uint, ctypes.c_ulonglong,
                ctypes.c_char_p, ctypes.c_ulonglong,
                ctypes.c_ulonglong]
            lib.wp_append.restype = ctypes.c_longlong
            lib.wp_drain.argtypes = [ctypes.c_int, ctypes.c_uint,
                                     ctypes.POINTER(WpEntry),
                                     ctypes.c_int]
            lib.wp_drain.restype = ctypes.c_int
            lib.wp_pending.argtypes = [ctypes.c_int, ctypes.c_uint]
            lib.wp_pending.restype = ctypes.c_int
            lib.wp_tail.argtypes = [ctypes.c_int, ctypes.c_uint]
            lib.wp_tail.restype = ctypes.c_ulonglong
            lib.wp_wait_epoch.argtypes = [
                ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint),
                ctypes.POINTER(ctypes.c_ulonglong)]
            lib.wp_wait_epoch.restype = ctypes.c_int
            lib.wp_epoch_done.argtypes = [ctypes.c_int, ctypes.c_uint,
                                          ctypes.c_ulonglong]
            lib.wp_requests.argtypes = [ctypes.c_int]
            lib.wp_requests.restype = ctypes.c_ulonglong
            lib.wp_fallbacks.argtypes = [ctypes.c_int]
            lib.wp_fallbacks.restype = ctypes.c_ulonglong
            lib.wp_latency.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_ulonglong)]
            lib.wp_latency.restype = ctypes.c_int
            _bind_record_drain(lib, "wp")
        except (OSError, subprocess.SubprocessError):
            return None
        _wp_lib = lib
        return _wp_lib


# -- meta-plane library (meta_plane.cc) --------------------------------

_MP_SRC = os.path.join(_DIR, "meta_plane.cc")
_MP_SO = os.path.join(_DIR, "_build", "libmeta_plane.so")
_POOL_H = os.path.join(_DIR, "plane_pool.h")
_mp_lib = None
_mp_tried = False


def load_meta_plane() -> "ctypes.CDLL | None":
    """Build (if needed) + load the native filer meta plane; None when
    unavailable — the filer then serves every write from Python (the
    same graceful-degradation contract as the volume write plane)."""
    global _mp_lib, _mp_tried
    with _lock:
        if _mp_lib is not None or _mp_tried:
            return _mp_lib
        _mp_tried = True
        try:
            if _build_if_stale(_MP_SRC, _MP_SO,
                               deps=[_POOL_H]) is None:
                return None
            lib = ctypes.CDLL(_MP_SO)
            lib.mp_start.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
                ctypes.c_char_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_int)]
            lib.mp_start.restype = ctypes.c_int
            lib.mp_stop.argtypes = [ctypes.c_int]
            lib.mp_arm.argtypes = [ctypes.c_int, ctypes.c_int]
            lib.mp_feed_fids.argtypes = [ctypes.c_int, ctypes.c_char_p]
            lib.mp_feed_fids.restype = ctypes.c_int
            lib.mp_fid_level.argtypes = [ctypes.c_int]
            lib.mp_fid_level.restype = ctypes.c_int
            lib.mp_mark_dir.argtypes = [ctypes.c_int, ctypes.c_char_p]
            lib.mp_mark_path.argtypes = [ctypes.c_int, ctypes.c_char_p]
            lib.mp_clear_dirs.argtypes = [ctypes.c_int]
            lib.mp_requests.argtypes = [ctypes.c_int]
            lib.mp_requests.restype = ctypes.c_ulonglong
            lib.mp_fallbacks.argtypes = [ctypes.c_int]
            lib.mp_fallbacks.restype = ctypes.c_ulonglong
            lib.mp_latency.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_ulonglong)]
            lib.mp_latency.restype = ctypes.c_int
            lib.mp_stats.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_ulonglong)]
            lib.mp_stats.restype = ctypes.c_int
            _bind_record_drain(lib, "mp")
            lib.mp_set_upload_delay_ms.argtypes = [ctypes.c_int,
                                                   ctypes.c_int]
        except (OSError, subprocess.SubprocessError):
            return None
        _mp_lib = lib
        return _mp_lib


# -- filer-read-plane library (filer_read_plane.cc) --------------------

_FRP_SRC = os.path.join(_DIR, "filer_read_plane.cc")
_FRP_SO = os.path.join(_DIR, "_build", "libfiler_read_plane.so")
_frp_lib = None
_frp_tried = False


def load_filer_read_plane() -> "ctypes.CDLL | None":
    """Build (if needed) + load the native filer read plane; None when
    unavailable — the filer then serves every read from Python (same
    graceful-degradation contract as the meta plane)."""
    global _frp_lib, _frp_tried
    with _lock:
        if _frp_lib is not None or _frp_tried:
            return _frp_lib
        _frp_tried = True
        try:
            if _build_if_stale(_FRP_SRC, _FRP_SO,
                               deps=[_POOL_H]) is None:
                return None
            lib = ctypes.CDLL(_FRP_SO)
            lib.frp_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                      ctypes.POINTER(ctypes.c_int)]
            lib.frp_start.restype = ctypes.c_int
            lib.frp_stop.argtypes = [ctypes.c_int]
            lib.frp_arm.argtypes = [ctypes.c_int, ctypes.c_int]
            lib.frp_gen.argtypes = [ctypes.c_int]
            lib.frp_gen.restype = ctypes.c_ulonglong
            lib.frp_put_entry.argtypes = [
                ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_ulonglong,
                ctypes.c_ulonglong]
            lib.frp_put_entry.restype = ctypes.c_int
            lib.frp_invalidate.argtypes = [ctypes.c_int,
                                           ctypes.c_char_p]
            lib.frp_clear.argtypes = [ctypes.c_int]
            lib.frp_entries.argtypes = [ctypes.c_int]
            lib.frp_entries.restype = ctypes.c_int
            lib.frp_requests.argtypes = [ctypes.c_int]
            lib.frp_requests.restype = ctypes.c_ulonglong
            lib.frp_fallbacks.argtypes = [ctypes.c_int]
            lib.frp_fallbacks.restype = ctypes.c_ulonglong
            lib.frp_latency.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_ulonglong)]
            lib.frp_latency.restype = ctypes.c_int
            lib.frp_stats.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_ulonglong)]
            lib.frp_stats.restype = ctypes.c_int
            _bind_record_drain(lib, "frp")
            lib.frp_set_fetch_delay_ms.argtypes = [ctypes.c_int,
                                                   ctypes.c_int]
        except (OSError, subprocess.SubprocessError):
            return None
        _frp_lib = lib
        return _frp_lib


_VT_SRC = os.path.join(os.path.dirname(__file__), "volume_tool.cc")
_VT_BIN = os.path.join(_DIR, "_build", "volume_tool")


def build_volume_tool() -> "str | None":
    """Build (if stale) the standalone C++ volume codec tool — the
    second implementation of the .dat/.idx storage surface (N1
    cross-impl parity role).  Returns the binary path or None when
    the toolchain is unavailable."""
    with _lock:
        return _build_if_stale(_VT_SRC, _VT_BIN, shared=False)
