"""Process-level cluster framework — the analog of the reference's
test/volume_server/framework: real `python -m seaweedfs_tpu` server
PROCESSES (not in-process objects), security/config profiles, port
polling, and kill -9 fault injection.

In-process tests can't catch classes of bugs that only exist across
real process boundaries: state that silently survives in module
globals, fds inherited across roles, graceful-shutdown paths that
never run under SIGKILL.  This rig boots the CLI the way an operator
does and murders processes the way hardware does."""

from __future__ import annotations

import itertools
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# config profiles (framework/matrix/config_profiles.go role): each is
# a security.toml body (empty = open cluster) applied to EVERY role
PROFILES = {
    "open": "",
    "jwt": """
[jwt.signing]
key = "proc-matrix-signing-key"
[jwt.signing.read]
key = ""
[access]
ui = false
""",
    # read-path tokens too: every GET must carry a read jwt the
    # volume server validates (security.py read gate)
    "jwt_read": """
[jwt.signing]
key = "proc-matrix-signing-key"
[jwt.signing.read]
key = "proc-matrix-read-key"
""",
    # admin-plane key: /admin/*, heartbeat, grow, lock are gated
    "admin": """
[admin]
key = "proc-matrix-admin-key"
""",
    # QoS plane armed from the [qos] security.toml section (qos.py):
    # generous default tenant budget, a capped "noisy" tenant, and a
    # foreground-SLO-driven EC throttle — the soak long run's profile
    "qos": """
[qos]
enabled = true
slo_p99_ms = 500
pace_min_ms = 25
pace_max_ms = 1000

[qos.default]
rps = 500
burst = 1000

[qos.tenants.noisy]
rps = 6
burst = 6
inflight_mb = 4
""",
    # mTLS: minted per-cluster PKI — ProcCluster fills in the
    # certificate paths (the {dir} placeholders) after running the
    # `cert` CLI; every role serves https and pins the CA
    "tls": """
[jwt.signing]
key = "proc-matrix-signing-key"
[tls]
ca = "{dir}/ca.crt"
cert = "{dir}/node.crt"
key = "{dir}/node.key"
mtls = true
""",
}


_ports = itertools.count()


def free_port() -> int:
    """A port for a role that binds it LATER, in another process,
    seconds from now — so never one of the kernel's own range
    (/proc/sys/net/ipv4/ip_local_port_range, 32768 up): a port probed
    there with bind(0) and let go is handed to the next bind(0) of any
    process on the machine (every in-process test server of the other
    xdist workers), the role then dies of EADDRINUSE and wait_port()
    is answered by the stranger.  Each worker walks a block of its
    own below that range; the bind is the check that nothing else
    holds the port now."""
    worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")[2:]
    base = 10000 + 1000 * (int(worker or 0) % 20)
    for _ in range(1000):
        port = base + next(_ports) % 1000
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise RuntimeError(f"no free port in {base}..{base + 999}")


def wait_port(port: int, timeout: float = 45.0,
              popen: "subprocess.Popen | None" = None) -> None:
    """Startup on this 1-core box is slow; poll, never fixed-sleep.
    With `popen`, the port must be opened by THAT process: one that
    exited (its port was taken) is an error, whoever answers there."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if popen is not None and popen.poll() is not None:
            raise RuntimeError(
                f"process for port {port} exited {popen.returncode} "
                "before it listened")
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=1.0):
                return
        except OSError:
            time.sleep(0.15)
    raise TimeoutError(f"port {port} never opened")


class Proc:
    """One server process with its role, port, and restart recipe."""

    def __init__(self, role: str, args: list, port: int,
                 log_path: str, env_extra: "dict | None" = None):
        self.role = role
        self.args = args
        self.port = port
        self.log_path = log_path
        self.env_extra = env_extra or {}
        self.popen: "subprocess.Popen | None" = None

    def start(self) -> "Proc":
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                   **self.env_extra)
        if getattr(self, "log_f", None) is not None and \
                not self.log_f.closed:
            self.log_f.close()   # kill9()+start() must not leak fds
        self.log_f = open(self.log_path, "ab")
        self.popen = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", *self.args],
            cwd=REPO, env=env, stdout=self.log_f,
            stderr=subprocess.STDOUT)
        wait_port(self.port, popen=self.popen)
        return self

    def kill9(self) -> None:
        """SIGKILL — no graceful shutdown, no flush, no deregister."""
        if self.popen is not None:
            self.popen.send_signal(signal.SIGKILL)
            self.popen.wait(timeout=10)
            self.popen = None

    def stop(self) -> None:
        if self.popen is not None:
            self.popen.terminate()
            try:
                self.popen.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.popen.kill()
                self.popen.wait(timeout=5)
            self.popen = None
        self.log_f.close()

    @property
    def url(self) -> str:
        return f"127.0.0.1:{self.port}"


class ProcCluster:
    """master + N volume servers + filer as real processes under one
    temp dir, with an optional security profile."""

    def __init__(self, tmp: str, volumes: int = 2,
                 profile: str = "open",
                 volume_size_limit_mb: int = 8):
        self.tmp = str(tmp)
        self.procs: dict[str, Proc] = {}
        sec_args = []
        if PROFILES.get(profile):
            body = PROFILES[profile]
            if "{dir}" in body:
                # mint the cluster PKI through the real CLI (the
                # `cert` command), then point the toml at it
                cert_dir = os.path.join(self.tmp, "certs")
                subprocess.run(
                    [sys.executable, "-m", "seaweedfs_tpu", "cert",
                     "-dir", cert_dir, "-hosts", "127.0.0.1"],
                    check=True, capture_output=True, timeout=120,
                    cwd=REPO,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"))
                body = body.replace("{dir}", cert_dir)
            sec_path = os.path.join(self.tmp, "security.toml")
            with open(sec_path, "w") as f:
                f.write(body)
            sec_args = ["-securityToml", sec_path]
        self.sec_args = sec_args
        self.profile = profile

        mport = free_port()
        mdir = os.path.join(self.tmp, "master-meta")
        os.makedirs(mdir, exist_ok=True)
        self.procs["master"] = Proc(
            "master", [*sec_args, "master", "-port", str(mport),
                       "-mdir", mdir,
                       "-volumeSizeLimitMB",
                       str(volume_size_limit_mb)], mport,
            os.path.join(self.tmp, "master.log"),
            env_extra=self._lockgraph_env("master"))
        for i in range(volumes):
            vport = free_port()
            vdir = os.path.join(self.tmp, f"vol{i}")
            os.makedirs(vdir, exist_ok=True)
            self.procs[f"volume{i}"] = Proc(
                f"volume{i}",
                [*sec_args, "volume", "-port", str(vport), "-dir",
                 vdir, "-mserver", f"127.0.0.1:{mport}"], vport,
                os.path.join(self.tmp, f"vol{i}.log"),
                env_extra=self._lockgraph_env(f"volume{i}"))
        fport = free_port()
        self.procs["filer"] = Proc(
            "filer", [*sec_args, "filer", "-port", str(fport),
                      "-master", f"127.0.0.1:{mport}",
                      "-store", os.path.join(self.tmp, "filer.db")],
            fport, os.path.join(self.tmp, "filer.log"),
            env_extra=self._lockgraph_env("filer"))

    def _lockgraph_env(self, role: str) -> dict:
        """Every server role runs under the devtools/lockgraph.py
        race detector: lock-order cycles found while the cluster
        serves real traffic land in per-role report files that
        lock_violations() aggregates (tier-1 doubles as a race
        harness)."""
        return {
            "WEED_LOCKGRAPH": "1",
            "WEED_LOCKGRAPH_OUT": os.path.join(
                self.tmp, f"lockgraph-{role}.json"),
        }

    def lock_violations(self, kind: str = "lock-order-cycle") -> list:
        """Aggregate detector findings across every role's report."""
        import json
        out = []
        for role in self.procs:
            path = os.path.join(self.tmp, f"lockgraph-{role}.json")
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue          # role never booted / mid-rewrite
            for v in doc.get("violations", []):
                if not kind or v.get("kind") == kind:
                    out.append(dict(v, role=role))
        return out

    def start(self) -> "ProcCluster":
        # a later role failing to boot must not orphan the earlier
        # ones (the caller has no handle yet to stop them with)
        try:
            self.procs["master"].start()
            for name, p in self.procs.items():
                if name.startswith("volume"):
                    p.start()
            self.procs["filer"].start()
        except Exception:
            self.stop()
            raise
        return self

    def stop(self) -> None:
        for p in reversed(list(self.procs.values())):
            try:
                p.stop()
            except Exception:
                pass

    @property
    def master(self) -> str:
        return self.procs["master"].url

    @property
    def filer(self) -> str:
        return self.procs["filer"].url

    def log_tail(self, role: str, n: int = 2000) -> str:
        with open(self.procs[role].log_path, "rb") as f:
            f.seek(0, 2)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
