"""`httpd.http_upload` (ISSUE 26): a file goes to the socket as a file —
`os.sendfile` on a plain connection, one reused 1 MiB buffer under
TLS — with everything the old urllib path did kept: exact
Content-Length, auth / trace / deadline headers, the per-operation
timeout, and the receiver's own verdict when it rejects mid-body.
Counts and bytes only; a speed is the chip's to say."""

import hashlib
import os
import socket
import ssl
import threading
import time

import pytest

from conftest import needs_crypto
from seaweedfs_tpu import security as sec_mod
from seaweedfs_tpu import tracing
from seaweedfs_tpu.security import SecurityConfig
from seaweedfs_tpu.server.httpd import HttpServer, http_upload
from seaweedfs_tpu.util import deadline as _dl
from seaweedfs_tpu.util.request_id import (reset_request_id,
                                           set_request_id)

MIB = 1 << 20
PLANES = ["plain", pytest.param("tls", marks=needs_crypto)]


@pytest.fixture
def receiver(request, tmp_path):
    """(server, what it saw): `/admin/take` digests the body it streams,
    `/admin/reject?status=&after=` answers mid-body after `after` bytes
    and lets the server close, `/admin/stall` reads nothing.  `plane`
    = "tls" puts the whole process on the TLS plane first."""
    plane = getattr(request, "param", "plain")
    if plane == "tls":
        from seaweedfs_tpu.tls import TlsConfig, generate_cluster_certs
        paths = generate_cluster_certs(str(tmp_path / "pki"))
        sec_mod.configure(SecurityConfig(tls=TlsConfig(
            ca_cert=paths["ca"], cert=paths["cert"], key=paths["key"],
            require_client_cert=True)))
    http = HttpServer("127.0.0.1", 0)
    seen = {"release": threading.Event()}

    def take(req):
        seen["headers"] = dict(req.headers)
        digest, n = hashlib.sha256(), 0
        for chunk in req.stream_body():
            digest.update(chunk)
            n += len(chunk)
        seen["bytes"], seen["sha256"] = n, digest.hexdigest()
        return 200, {"bytes": n}

    def reject(req):
        after, n = int(req.query["after"]), 0
        if after:
            for chunk in req.stream_body(chunk_size=MIB):
                n += len(chunk)
                if n >= after:
                    break
        seen["bytes"] = n
        return int(req.query["status"]), {"error": "no room", "read": n}

    def stall(req):
        seen["release"].wait(20)
        return 200, {}

    http.route("POST", "/admin/take", take)
    http.route("POST", "/admin/reject", reject)
    http.route("POST", "/admin/stall", stall)
    http.start()
    yield http, seen
    seen["release"].set()
    http.stop()
    sec_mod.configure(None)


@pytest.fixture
def client_sends(receiver, monkeypatch):
    """(call, length) of every `send` / `sendall` a client socket of
    this process makes towards the receiver, in order (the server's
    own replies go the other way and are left out)."""
    http, _seen = receiver
    calls = []

    def counted(name, orig):
        def call(self, data, *args):
            try:
                ours = self.getpeername()[1] == http.port
            except OSError:
                ours = False
            if ours:
                calls.append((name, len(data)))
            return orig(self, data, *args)
        return call

    for cls in (socket.socket, ssl.SSLSocket):
        for name in ("send", "sendall"):
            monkeypatch.setattr(cls, name,
                                counted(name, getattr(cls, name)))
    return calls


def _file(tmp_path, size: int, sparse: bool = False):
    path = tmp_path / f"body{size}.bin"
    with open(path, "wb") as f:
        if sparse:
            f.truncate(size)
        else:
            f.write(os.urandom(size))
    return str(path)


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
@pytest.mark.parametrize("size", [9 * MIB + 1, 0])
def test_a_file_arrives_whole_by_the_planes_own_call(
        receiver, client_sends, tmp_path, size):
    http, seen = receiver
    tls = sec_mod.current().tls is not None
    path = _file(tmp_path, size)
    res = http_upload("POST", f"{http.url}/admin/take", path)
    status, body, headers = res
    assert status == 200 and b'"bytes"' in body
    assert headers["Content-Type"].startswith("application/json")
    assert seen["headers"]["Content-Length"] == str(size)
    assert seen["bytes"] == size
    with open(path, "rb") as f:
        assert seen["sha256"] == hashlib.sha256(f.read()).hexdigest()
    # the request line and headers are one sendall (which under TLS
    # is SSLSocket.sendall handing its bytes on to SSLSocket.send);
    # whatever else was sent that way is the body
    assert client_sends[0][0] == "sendall"
    if tls:
        assert res.via == "blocks"
        pieces = [n for name, n in client_sends[1:] if name == "sendall"]
        assert sum(pieces) == size
        assert len(pieces) <= size // MIB + 2
    else:
        assert res.via == "sendfile"
        assert client_sends[1:] == []


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
@pytest.mark.parametrize("status,after,size", [
    (400, 0, 65 * MIB),        # refused unread: too large to drain
    (507, 2 * MIB, 48 * MIB),  # the disk filled two pieces in
])
def test_a_receiver_that_rejects_mid_body_is_heard(
        receiver, tmp_path, status, after, size):
    http, seen = receiver
    path = _file(tmp_path, size, sparse=True)
    got, body, _headers = http_upload(
        "POST",
        f"{http.url}/admin/reject?status={status}&after={after}",
        path, timeout=20)
    assert got == status
    assert b"no room" in body
    assert after <= seen["bytes"] < size


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
def test_auth_trace_and_deadline_headers_still_arrive(
        receiver, tmp_path):
    http, seen = receiver
    tls = sec_mod.current().tls
    sec_mod.configure(SecurityConfig(admin_key="k-upload", tls=tls))
    token = set_request_id("upload-26")
    try:
        with tracing.span("caller", role="worker") as caller, \
                _dl.scope(30.0):
            status, _, _ = http_upload(
                "POST", f"{http.url}/admin/take",
                _file(tmp_path, 3000), headers={"X-Mine": "kept"})
    finally:
        reset_request_id(token)
    assert status == 200
    got = seen["headers"]
    assert got["X-Mine"] == "kept"
    assert got["Authorization"].startswith("Bearer ")
    assert sec_mod.current().check_admin({}, got) is None
    assert got["X-Request-ID"] == "upload-26"
    assert got[tracing.HEADER] == f"{caller.trace_id}-{caller.span_id}"
    assert 0 < float(got[_dl.HEADER]) <= 30_000
    assert got["Content-Length"] == "3000"


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
@pytest.mark.parametrize("budget", [None, 0.5])
def test_a_stalled_receiver_trips_the_per_operation_timeout(
        receiver, tmp_path, budget):
    """More bytes than the socket buffers hold, to a handler that reads
    none: the send stalls and the stall bound (the caller's, or the
    armed budget's where that is shorter) ends it."""
    http, seen = receiver
    path = _file(tmp_path, 64 * MIB, sparse=True)
    t0 = time.monotonic()
    with pytest.raises(OSError) as err:
        if budget is None:
            http_upload("POST", f"{http.url}/admin/stall", path,
                        timeout=0.5)
        else:
            with _dl.scope(budget):
                http_upload("POST", f"{http.url}/admin/stall", path)
    assert isinstance(err.value, TimeoutError), err.value
    assert time.monotonic() - t0 < 10


def test_a_file_cut_short_while_it_goes_fails_the_upload(
        receiver, tmp_path, monkeypatch):
    """A push that ends short must fail: the file is cut to half under
    the sender, and the body stops at fewer bytes than the
    Content-Length promised."""
    http, seen = receiver
    path = _file(tmp_path, 4 * MIB, sparse=True)
    real = os.sendfile

    def cut_then_send(out, fd, offset, count):
        os.truncate(path, 2 * MIB)
        return real(out, fd, offset, count)

    monkeypatch.setattr(os, "sendfile", cut_then_send)
    with pytest.raises(OSError, match="ended at 2097152 of 4194304"):
        http_upload("POST", f"{http.url}/admin/take", path, timeout=5)
    assert "sha256" not in seen
