"""Process-level cluster tests (test/volume_server/framework shape):
real CLI server processes, config/security matrix, kill -9 fault
injection.  Everything here crosses true process boundaries — the
failure modes in-process harnesses structurally cannot produce."""

import time

import pytest

from seaweedfs_tpu import operation
from seaweedfs_tpu.server.httpd import http_bytes, http_json

from proc_framework import PROFILES, ProcCluster


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = ProcCluster(tmp_path_factory.mktemp("proc"), volumes=2).start()
    # volumes need a heartbeat round before assigns succeed
    _wait_writable(c)
    yield c
    c.stop()


def _wait_writable(c, timeout=30):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            st, body, _ = http_bytes(
                "GET", f"{c.master}/cluster/status")
            if st == 200:
                fid = operation.submit(c.master, b"probe")
                assert operation.read(c.master, fid) == b"probe"
                return
        except Exception as e:  # noqa: BLE001
            last = e
        time.sleep(0.3)
    raise TimeoutError(f"cluster never writable: {last}")


def test_blob_write_read_across_processes(cluster):
    fid = operation.submit(cluster.master, b"process-level blob")
    assert operation.read(cluster.master, fid) == \
        b"process-level blob"


def test_filer_write_read_across_processes(cluster):
    st, _, _ = http_bytes(
        "POST", f"http://{cluster.filer}/dir/hello.txt",
        b"via the filer process")
    assert st < 300
    st, body, _ = http_bytes(
        "GET", f"http://{cluster.filer}/dir/hello.txt")
    assert st == 200 and body == b"via the filer process"


def test_volume_server_kill9_then_restart_serves_data(cluster):
    """SIGKILL a volume server holding live data: no graceful flush
    ran, yet after restart the append-only .dat/.idx recover it."""
    data = b"survives SIGKILL" * 100
    fid = operation.submit(cluster.master, data)
    vid = int(fid.split(",")[0])
    locs = http_json("GET",
                     f"http://{cluster.master}/dir/lookup?volumeId={vid}")
    url = locs["locations"][0]["url"]
    victim = next(p for name, p in cluster.procs.items()
                  if name.startswith("volume") and p.url == url)
    victim.kill9()
    victim.start()
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if operation.read(cluster.master, fid) == data:
                break
        except Exception:  # noqa: BLE001 — re-registering
            pass
        time.sleep(0.3)
    assert operation.read(cluster.master, fid) == data


def test_master_kill9_restart_keeps_identity_no_fid_reuse(cluster):
    """SIGKILL the master: the persisted raft log restores topology
    identity and the fid sequence after restart — a new assign must
    not reuse a pre-crash fid."""
    before = http_json("GET",
                       f"http://{cluster.master}/cluster/status")
    fid1 = operation.submit(cluster.master, b"pre-crash")
    master = cluster.procs["master"]
    master.kill9()
    master.start()
    deadline = time.time() + 45
    fid2 = None
    while time.time() < deadline:
        try:
            fid2 = operation.submit(cluster.master, b"post-crash")
            break
        except Exception:  # noqa: BLE001 — heartbeats re-register
            time.sleep(0.4)
    assert fid2 is not None, "master never writable after restart"
    # compare the NEEDLE KEY, not the fid string: the cookie is random
    # per assign, so the strings always differ even when the sequencer
    # reuses a key — exactly the bug this test exists to catch
    def needle_key(fid):
        return int(fid.split(",")[1][:-8], 16)
    assert needle_key(fid2) != needle_key(fid1)
    after = http_json("GET",
                      f"http://{cluster.master}/cluster/status")
    assert after.get("topologyId") == before.get("topologyId")
    # pre-crash data still readable
    assert operation.read(cluster.master, fid1) == b"pre-crash"


def test_filer_kill9_restart_namespace_survives(cluster):
    # the write itself is retried with a deadline: on an oversubscribed
    # box the freshly-started cluster can still be registering volume
    # heartbeats, so the first assign may 5xx — that's the startup
    # window, not the durability property under test
    deadline = time.time() + 45
    st = 0
    while time.time() < deadline:
        try:
            st, _, _ = http_bytes(
                "POST", f"http://{cluster.filer}/crash/file.txt",
                b"filer durability")
        except OSError:
            st = 0
        if st < 300 and st != 0:
            break
        time.sleep(0.4)
    assert st < 300 and st != 0, \
        f"filer never accepted the pre-crash write (last status {st})"
    filer = cluster.procs["filer"]
    filer.kill9()
    filer.start()
    deadline = time.time() + 60
    st, body = 0, b""
    while time.time() < deadline:
        try:
            st, body, _ = http_bytes(
                "GET", f"http://{cluster.filer}/crash/file.txt")
        except OSError:
            # the listener is not back yet — connection refused is
            # part of the restart window, not a failure
            st, body = 0, b""
        if st == 200 and body == b"filer durability":
            break
        time.sleep(0.3)
    assert st == 200 and body == b"filer durability"


@pytest.mark.parametrize("profile", sorted(PROFILES))
def test_config_matrix_write_read(tmp_path, profile):
    """The same smoke under every security profile
    (framework/matrix/config_profiles.go): open, jwt (per-fid write
    tokens), jwt_read (read tokens too), admin (admin-plane key), and
    tls (mTLS with a minted PKI) must all serve the full write/read
    path.  The CLIENT side loads the same security.toml the roles
    did — the reference's matrix drives its clients the same way."""
    from seaweedfs_tpu import security
    if profile == "tls":
        # the tls profile mints a PKI via the `cert` CLI, which needs
        # the cryptography package — absent in some containers
        pytest.importorskip("cryptography")
    c = ProcCluster(tmp_path, volumes=1, profile=profile).start()
    sec_path = f"{tmp_path}/security.toml"
    try:
        if PROFILES.get(profile):
            # inside the try: a toml load error must still stop the
            # started cluster processes
            security.configure(security.load_security_toml(sec_path))
        _wait_writable(c)
        fid = operation.submit(c.master, b"matrix " + profile.encode())
        assert operation.read(c.master, fid) == \
            b"matrix " + profile.encode()
        # bare host:port lets the client funnel pick the scheme the
        # security config mandates (https + pinned CA under tls)
        st, _, _ = http_bytes(
            "POST", f"{c.filer}/m/{profile}.txt", b"filer-ok")
        assert st < 300
        st, body, _ = http_bytes(
            "GET", f"{c.filer}/m/{profile}.txt")
        assert st == 200 and body == b"filer-ok"
        if profile == "jwt":
            # an unsigned direct volume write must be REFUSED
            locs = http_json(
                "GET", f"http://{c.master}/dir/lookup?volumeId="
                       f"{int(fid.split(',')[0])}")
            url = locs["locations"][0]["url"]
            st, _, _ = http_bytes("POST", f"http://{url}/{fid}",
                                  b"unsigned overwrite")
            assert st in (401, 403), \
                f"unsigned write accepted under jwt profile: {st}"
        if profile == "admin":
            # an UNKEYED admin-plane call must be refused (raw
            # urllib: the configured client funnel would auto-attach
            # the admin jwt and mask the gate)
            import urllib.error
            import urllib.request
            locs = http_json(
                "GET", f"{c.master}/dir/lookup?volumeId="
                       f"{int(fid.split(',')[0])}")
            url = locs["locations"][0]["url"]
            req = urllib.request.Request(
                f"http://{url}/admin/vacuum",
                data=b'{"volumeId": 1}', method="POST",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    raise AssertionError(
                        f"unkeyed admin call accepted: {r.status}")
            except urllib.error.HTTPError as e:
                assert e.code in (401, 403), e.code
        if profile == "jwt_read":
            # an unsigned direct volume READ must be refused
            locs = http_json(
                "GET", f"http://{c.master}/dir/lookup?volumeId="
                       f"{int(fid.split(',')[0])}")
            url = locs["locations"][0]["url"]
            import urllib.request
            try:
                with urllib.request.urlopen(
                        f"http://{url}/{fid}", timeout=10) as r:
                    assert r.status in (401, 403), \
                        "unsigned read accepted under jwt_read"
            except urllib.error.HTTPError as e:
                assert e.code in (401, 403), e.code
        if profile == "tls":
            # a plain-TCP client must be REFUSED by the tls cluster
            import urllib.error
            import urllib.request
            import http.client
            try:
                urllib.request.urlopen(
                    f"http://{c.filer}/m/{profile}.txt", timeout=10)
                raise AssertionError("plaintext accepted under tls")
            except (urllib.error.URLError, ConnectionError, OSError,
                    http.client.HTTPException):
                # a TLS alert read as a garbage status line raises
                # BadStatusLine (HTTPException), equally a refusal
                pass
    finally:
        security.configure(None)
        c.stop()


def test_volume_role_mounts_ec_volume_without_importing_jax(cluster):
    """One process owns the accelerator (ec_context.own_device), and a
    volume server is not it: encoding, mounting and reading an EC
    volume in a spawned `volume` role resolves ECContext() to the host
    engine without importing jax — on a chip machine an import alone
    would load libtpu and race the worker for the device."""
    from seaweedfs_tpu.shell import CommandEnv, run_command
    blob = b"ec in a role that owns no chip " * 4000
    fid = operation.submit(cluster.master, blob, collection="noxla")
    vid = int(fid.split(",")[0])
    env = CommandEnv(cluster.master)
    run_command(env, "lock")
    try:
        out = run_command(
            env, f"ec.encode -volumeId={vid} -collection=noxla")
    finally:
        run_command(env, "unlock")
    assert f"volume {vid}" in out, out
    assert operation.read(cluster.master, fid) == blob
    for name, proc in cluster.procs.items():
        with open(f"/proc/{proc.popen.pid}/maps") as f:
            maps = f.read()
        assert "jaxlib" not in maps and "libtpu" not in maps, \
            f"{name} loaded jax"


def test_no_lock_order_cycles_under_traffic(cluster):
    """The cluster fixture runs every role under the lockgraph race
    detector (devtools/lockgraph.py); after the write/read/kill9
    traffic of the tests above, no role may have recorded a lock-order
    cycle (potential deadlock).  Report files flush continuously, so
    reading them while the cluster is live is safe."""
    # drive a little more mixed traffic through every plane first
    for i in range(5):
        fid = operation.submit(cluster.master, f"race-{i}".encode())
        assert operation.read(cluster.master, fid) == f"race-{i}".encode()
    time.sleep(1.5)     # one detector flush interval
    cycles = cluster.lock_violations("lock-order-cycle")
    assert cycles == [], f"lock-order cycles detected: {cycles}"
