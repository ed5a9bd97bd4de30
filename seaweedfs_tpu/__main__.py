"""CLI entry: `python -m seaweedfs_tpu <command>` — the analog of the
reference's single multi-command `weed` binary (weed/weed.go:50,
weed/command/command.go:11-51).

Commands: master, volume, server (all-in-one), shell, upload, download,
bench.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv: list[str] | None = None) -> int:
    # WEED_LOCKGRAPH=1 race harness: must patch lock factories before
    # any server object is constructed (devtools/lockgraph.py)
    from .devtools.lockgraph import maybe_instrument
    maybe_instrument()
    # every role is an IO-chained thread server (handler threads block
    # on sockets between short CPU bursts); CPython's default 5ms GIL
    # switch interval adds a convoy delay to EVERY hop's response
    # wakeup, which multiplies across the client->filer->master->
    # volume chain.  1ms costs negligible context-switch overhead at
    # our thread counts and measurably compresses per-hop latency.
    import sys as _sys
    _sys.setswitchinterval(0.001)
    p = argparse.ArgumentParser(prog="seaweedfs-tpu")
    # security.toml discovery (util/config.go:34
    # LoadSecurityConfiguration; scaffold command/scaffold/security.toml)
    p.add_argument("-securityToml", default="",
                   help="path to security.toml (jwt signing keys, "
                        "admin key, ip whitelist)")
    # glog-analog logging flags (util/wlog; weed/glog -v/-logdir)
    p.add_argument("-v", type=int, default=None, metavar="LEVEL",
                   help="verbose log level (wlog.V gates; also "
                        "WEED_V)")
    p.add_argument("-logdir", default="",
                   help="also write logs to <logdir>/weed.log with "
                        "size rotation (glog_file.go role)")
    p.add_argument("-logJson", dest="log_json", action="store_true",
                   help="one JSON object per log line "
                        "(glog_json.go role)")
    sub = p.add_subparsers(dest="cmd", required=True)

    m = sub.add_parser("master", help="start a master server")
    m.add_argument("-ip", default="127.0.0.1")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-volumeSizeLimitMB", type=int, default=1024)
    m.add_argument("-defaultReplication", default="000")
    m.add_argument("-peers", default="",
                   help="comma-separated master peers for HA "
                        "(raft leader election + log replication)")
    m.add_argument("-mdir", default="",
                   help="meta dir: persists the raft log/snapshot so "
                        "topology id + fid sequence survive restarts")
    m.add_argument("-metricsAddress", dest="metrics_address",
                   default="", help="Prometheus pushgateway "
                   "host:port (stats/metrics.go LoopPushingMetric)")
    m.add_argument("-metricsIntervalSec", dest="metrics_interval",
                   type=int, default=15)
    m.add_argument("-telemetry", action="store_true",
                   help="OPT-IN anonymous usage reports "
                        "(weed/telemetry; default off)")
    m.add_argument("-telemetryUrl", dest="telemetry_url",
                   default="", help="collector URL for -telemetry")

    v = sub.add_parser("volume", help="start a volume server")
    v.add_argument("-ip", default="127.0.0.1")
    v.add_argument("-port", type=int, default=8080)
    v.add_argument("-dir", default=".", help="comma-separated data dirs")
    v.add_argument("-mserver", default="127.0.0.1:9333")
    v.add_argument("-max", type=int, default=64,
                   help="volumes this server offers: 64 of this repo's "
                        "1024 MiB default limit (upstream's 8 are of "
                        "30,000 MB each)")
    v.add_argument("-dataCenter", default="")
    v.add_argument("-rack", default="")
    v.add_argument("-tierBackend", default="",
                   help="S3 tier backend: endpoint,bucket[,accessKey,"
                        "secretKey] — lets this server reopen tiered "
                        "volumes after restart (master.toml "
                        "[storage.backend.s3] analog)")
    v.add_argument("-metricsAddress", dest="metrics_address",
                   default="", help="Prometheus pushgateway host:port")
    v.add_argument("-metricsIntervalSec", dest="metrics_interval",
                   type=int, default=15)
    v.add_argument("-memoryMapMaxSizeMb", dest="mmap_mb", type=int,
                   default=0,
                   help="mmap the .dat read path for volumes up to "
                        "this size (backend/memory_map role; 0 off)")
    v.add_argument("-fsync", action="store_true",
                   help="fsync acked writes (power-loss durability "
                        "tier; one fsync per group-commit window, "
                        "amortized across concurrent writers)")

    s = sub.add_parser(
        "server", help="all-in-one: master + volume (+ filer + s3), the "
        "weed server / weed mini analog (command/mini.go:894 "
        "dependency-ordered startup)")
    s.add_argument("-ip", default="127.0.0.1")
    s.add_argument("-master.port", dest="master_port", type=int,
                   default=9333)
    s.add_argument("-volume.port", dest="volume_port", type=int,
                   default=8080)
    s.add_argument("-filer", action="store_true")
    s.add_argument("-filer.port", dest="filer_port", type=int,
                   default=8888)
    s.add_argument("-s3", action="store_true")
    s.add_argument("-s3.port", dest="s3_port", type=int, default=8333)
    s.add_argument("-s3.accessKey", dest="s3_access", default="")
    s.add_argument("-s3.secretKey", dest="s3_secret", default="")
    s.add_argument("-dir", default=".")
    s.add_argument("-tierBackend", default="",
                   help="S3 tier backend: endpoint,bucket[,accessKey,"
                        "secretKey]")

    fl = sub.add_parser("filer", help="start a filer server")
    fl.add_argument("-ip", default="127.0.0.1")
    fl.add_argument("-port", type=int, default=8888)
    fl.add_argument("-master", default="127.0.0.1:9333")
    fl.add_argument("-store", default="filer.db",
                    help="store path (sqlite file / lsm dir), or "
                         ":memory:")
    fl.add_argument("-storeType", dest="store_type",
                    default="sqlite",
                    choices=["sqlite", "lsm", "redis", "elastic"],
                    help="metadata store archetype (filerstore.go: "
                         "sqlite=SQL, lsm=embedded ordered-KV — the "
                         "reference's leveldb default — redis=RESP "
                         "server at -store host:port); a filer.toml "
                         "on the config search path overrides these "
                         "defaults (util/config)")
    fl.add_argument("-collection", default="")
    fl.add_argument("-replication", default="")
    fl.add_argument("-notification", default="",
                    help="metadata notification sink "
                         "(weed/notification): webhook:http://...,"
                         " mq:broker/ns/topic, kafka:host:port/topic"
                         " (real Kafka wire protocol, any broker),"
                         " or logfile:/path")
    fl.add_argument("-lockPeers", dest="lock_peers", default="",
                    help="comma-separated filer addresses forming the "
                         "distributed-lock ring (give every filer the "
                         "same list; cluster/lock_manager)")
    fl.add_argument("-workers", type=int, default=None,
                    help="pre-fork worker processes sharing this "
                         "port via SO_REUSEPORT (sqlite store only: "
                         "one WAL store + one metalog dir, watermark-"
                         "coherent — the funnel past one process's "
                         "GIL).  Default 1; env "
                         "SEAWEEDFS_TPU_FILER_WORKERS sets it "
                         "cluster-wide.  0 marks a spawned worker "
                         "(internal).")
    fl.add_argument("-metaPlane", dest="meta_plane", default="",
                    choices=["", "0", "1"],
                    help="filer meta plane (metalog-as-WAL ack + "
                         "async store checkpointing, filer/"
                         "meta_plane.py): 1 forces on, 0 forces the "
                         "synchronous store commit; default auto "
                         "(on for durable sqlite/lsm stores).  Sets "
                         "SEAWEEDFS_TPU_FILER_META_PLANE so pre-fork "
                         "workers inherit it.")
    fl.add_argument("-metricsAddress", dest="metrics_address",
                    default="", help="Prometheus pushgateway "
                    "host:port (stats/metrics.go LoopPushingMetric)")
    fl.add_argument("-metricsIntervalSec", dest="metrics_interval",
                    type=int, default=15)

    s3p = sub.add_parser("s3", help="start the S3 gateway (on a filer)")
    s3p.add_argument("-ip", default="127.0.0.1")
    s3p.add_argument("-port", type=int, default=8333)
    s3p.add_argument("-master", default="127.0.0.1:9333")
    s3p.add_argument("-filer", default="",
                     help="attach to a RUNNING filer's namespace "
                          "(the reference's weed s3 -filer mode); "
                          "overrides -master/-store")
    s3p.add_argument("-store", default="filer.db")
    s3p.add_argument("-accessKey", default="")
    s3p.add_argument("-secretKey", default="")
    s3p.add_argument("-iamConfig", dest="iam_config", default="",
                     help="identities JSON (auth_credentials.go "
                          "s3.json shape); supersedes -accessKey")
    s3p.add_argument("-metricsPort", dest="metrics_port", type=int,
                     default=None,
                     help="serve per-bucket Prometheus metrics on a "
                          "SEPARATE listener (the reference's "
                          "weed s3 -metricsPort)")
    s3p.add_argument("-metricsAddress", dest="metrics_address",
                     default="", help="Prometheus pushgateway "
                     "host:port (stats/metrics.go LoopPushingMetric)")
    s3p.add_argument("-metricsIntervalSec", dest="metrics_interval",
                     type=int, default=15)
    s3p.add_argument("-stsKey", dest="sts_key", default="",
                     help="STS signing key: accept temporary "
                          "credentials minted by the iam server")
    s3p.add_argument("-rolesFile", dest="roles_file", default="")
    s3p.add_argument("-kmsFile", dest="kms_file", default="",
                     help="local KMS keystore (enables SSE-KMS)")
    s3p.add_argument("-kmsEndpoint", dest="kms_endpoint", default="",
                     help="remote AWS-KMS-protocol endpoint "
                          "host:port[,accessKey,secretKey[,region]] "
                          "(kms/aws analog); overrides -kmsFile")
    s3p.add_argument("-kmsCloud", dest="kms_cloud", default="",
                     help="cloud KMS spec (kms/gcp|azure|openbao): "
                          "gcp:endpoint,keyName,token | "
                          "azure:vaultUrl,keyName,token | "
                          "openbao:addr,keyName,token; overrides "
                          "-kmsEndpoint/-kmsFile")

    iamp = sub.add_parser(
        "iam", help="IAM management API + STS AssumeRole "
        "(weed/iamapi, weed/iam/sts) sharing an identities JSON with "
        "the s3 gateway")
    iamp.add_argument("-ip", default="127.0.0.1")
    iamp.add_argument("-port", type=int, default=8111)
    iamp.add_argument("-iamConfig", dest="iam_config", required=True)
    iamp.add_argument("-stsKey", dest="sts_key", default="")
    iamp.add_argument("-rolesFile", dest="roles_file", default="")
    iamp.add_argument("-oidcConfig", dest="oidc_config", default="",
                      help="JSON list of OIDC providers: [{name, "
                           "issuer, audience?, hs256Secret? | "
                           "rsaPublicKeyFile?}] — enables "
                           "AssumeRoleWithWebIdentity")

    ad = sub.add_parser("admin", help="start the maintenance admin server")
    ad.add_argument("-ip", default="127.0.0.1")
    ad.add_argument("-port", type=int, default=23646)
    ad.add_argument("-master", default="127.0.0.1:9333")
    ad.add_argument("-detectionInterval", type=float, default=30.0)
    ad.add_argument("-dataDir", default="",
                    help="persist jobs/config/workers under "
                         "<dataDir>/plugin/ (survives restart)")

    wk = sub.add_parser(
        "worker", help="start a maintenance worker (tpu_ec sidecar: owns "
        "the accelerator and executes erasure-coding jobs)")
    wk.add_argument("-admin", default="127.0.0.1:23646")
    wk.add_argument("-master", default="127.0.0.1:9333")
    wk.add_argument("-dir", default="/tmp/seaweedfs_tpu_worker")
    wk.add_argument("-capabilities", default="erasure_coding,vacuum")
    wk.add_argument("-backend", default="",
                    help="EC codec backend: jax|native|cpu (default: "
                         "the faster of the device and the host "
                         "codec on this machine)")

    wd = sub.add_parser("webdav", help="WebDAV gateway attached to a "
                        "running filer (server/webdav_server.go)")
    wd.add_argument("-ip", default="127.0.0.1")
    wd.add_argument("-port", type=int, default=7333)
    wd.add_argument("-filer", default="127.0.0.1:8888",
                    help="filer host:port whose namespace to serve")

    mnt = sub.add_parser(
        "mount", help="FUSE-mount a filer (read-only slice; "
        "weed/mount analog — see seaweedfs_tpu/mount/DESIGN.md)")
    mnt.add_argument("-filer", default="127.0.0.1:8888")
    mnt.add_argument("-dir", required=True, help="mountpoint")

    mqb = sub.add_parser(
        "mq.broker", help="start a message-queue broker "
        "(mq/broker/broker_server.go)")
    mqb.add_argument("-ip", default="127.0.0.1")
    mqb.add_argument("-port", type=int, default=17777)
    mqb.add_argument("-filer", default="127.0.0.1:8888")

    mqa = sub.add_parser(
        "mq.agent", help="MQ agent: session facade in front of the "
        "broker cluster (mq/agent/agent_server.go)")
    mqa.add_argument("-ip", default="127.0.0.1")
    mqa.add_argument("-port", type=int, default=16777)
    mqa.add_argument("-broker", default="127.0.0.1:17777")

    kgw = sub.add_parser(
        "mq.kafka", help="Kafka wire-protocol gateway over a running "
        "MQ broker (mq/kafka/gateway)")
    kgw.add_argument("-ip", default="127.0.0.1")
    kgw.add_argument("-port", type=int, default=9092)
    kgw.add_argument("-broker", default="127.0.0.1:17777")
    kgw.add_argument("-users", default="",
                     help="SASL/PLAIN credentials user:pass[,u2:p2] "
                          "— when set, clients must authenticate "
                          "before any data API")

    fsync = sub.add_parser(
        "filer.sync", help="continuously replicate one filer's "
        "namespace+content to another, resuming from a persisted "
        "offset (command/filer_sync.go)")
    fsync.add_argument("-from", dest="sync_from", required=True,
                       help="source filer host:port")
    fsync.add_argument("-to", dest="sync_to", required=True,
                       help="target filer host:port")
    fsync.add_argument("-state", default="",
                       help="offset checkpoint file (default: a "
                            "per-direction name derived from -from/-to)")
    fsync.add_argument("-interval", type=float, default=0.5,
                       help="poll interval seconds when idle")

    fbak = sub.add_parser(
        "filer.backup", help="continuously mirror a filer into a "
        "local directory (command/filer_backup.go)")
    fbak.add_argument("-filer", required=True,
                      help="source filer host:port")
    fbak.add_argument("-dir", required=True, help="backup root")
    fbak.add_argument("-state", default="",
                      help="offset checkpoint file")
    fbak.add_argument("-interval", type=float, default=0.5)

    fbs3 = sub.add_parser(
        "filer.backup.s3", help="continuously mirror a filer into an "
        "S3-compatible bucket (replication/sink/s3sink)")
    fbs3.add_argument("-filer", required=True,
                      help="source filer host:port")
    fbs3.add_argument("-endpoint", required=True,
                      help="S3 endpoint, e.g. http://host:8333")
    fbs3.add_argument("-bucket", required=True)
    fbs3.add_argument("-accessKey", dest="access_key", default="")
    fbs3.add_argument("-secretKey", dest="secret_key", default="")
    fbs3.add_argument("-prefix", default="",
                      help="key prefix inside the bucket")
    fbs3.add_argument("-state", default="",
                      help="offset checkpoint file")
    fbs3.add_argument("-interval", type=float, default=0.5)

    fbgcs = sub.add_parser(
        "filer.backup.gcs", help="continuously mirror a filer into a "
        "Google Cloud Storage bucket (replication/sink/gcssink)")
    fbgcs.add_argument("-filer", required=True)
    fbgcs.add_argument("-bucket", required=True)
    fbgcs.add_argument("-endpoint",
                       default="https://storage.googleapis.com",
                       help="override for emulators")
    fbgcs.add_argument("-token", default="",
                       help="OAuth bearer (or env GOOGLE_BEARER_TOKEN)")
    fbgcs.add_argument("-prefix", default="")
    fbgcs.add_argument("-state", default="")
    fbgcs.add_argument("-interval", type=float, default=0.5)

    fbaz = sub.add_parser(
        "filer.backup.azure", help="continuously mirror a filer into "
        "an Azure Blob container (replication/sink/azuresink)")
    fbaz.add_argument("-filer", required=True)
    fbaz.add_argument("-account", required=True)
    fbaz.add_argument("-accountKey", dest="account_key", required=True,
                      help="base64 shared key")
    fbaz.add_argument("-container", required=True)
    fbaz.add_argument("-endpoint", default="",
                      help="override for emulators (azurite)")
    fbaz.add_argument("-prefix", default="")
    fbaz.add_argument("-state", default="")
    fbaz.add_argument("-interval", type=float, default=0.5)

    fbb2 = sub.add_parser(
        "filer.backup.b2", help="continuously mirror a filer into a "
        "Backblaze B2 bucket (replication/sink/b2sink)")
    fbb2.add_argument("-filer", required=True)
    fbb2.add_argument("-keyId", dest="key_id", required=True)
    fbb2.add_argument("-appKey", dest="app_key", required=True)
    fbb2.add_argument("-bucket", required=True)
    fbb2.add_argument("-endpoint",
                      default="https://api.backblazeb2.com")
    fbb2.add_argument("-prefix", default="")
    fbb2.add_argument("-state", default="")
    fbb2.add_argument("-interval", type=float, default=0.5)

    sf = sub.add_parser(
        "sftp", help="SFTP gateway attached to a running filer "
        "(weed/sftpd; from-scratch SSH transport — no SSH lib in env)")
    sf.add_argument("-ip", default="127.0.0.1")
    sf.add_argument("-port", type=int, default=2022)
    sf.add_argument("-filer", default="127.0.0.1:8888")
    sf.add_argument("-userStoreFile", dest="user_store", required=True,
                    help="JSON user store (sftpd/user/filestore.go)")
    sf.add_argument("-hostKeyFile", dest="host_key", default="",
                    help="ed25519 host key PEM; generated+saved if "
                         "missing")
    sf.add_argument("-authMethods", dest="auth_methods",
                    default="password,publickey")
    sf.add_argument("-banner", default="")
    sf.add_argument("-ldapServer", dest="ldap_server", default="",
                    help="host:port of an LDAP server for password "
                         "auth (iam/ldap, ldap_provider.go analog)")
    sf.add_argument("-ldapUserDnTemplate", dest="ldap_dn_template",
                    default="",
                    help="user DN template, {} = username "
                         "(e.g. uid={},ou=people,dc=corp)")
    sf.add_argument("-ldapBaseDn", dest="ldap_base_dn", default="")
    sf.add_argument("-ldapBindDn", dest="ldap_bind_dn", default="")
    sf.add_argument("-ldapBindPassword", dest="ldap_bind_password",
                    default="")
    sf.add_argument("-ldapTls", dest="ldap_tls",
                    action="store_true",
                    help="reach the directory over TLS (ldaps) — "
                         "simple binds carry cleartext passwords, so "
                         "use this for any non-loopback server")

    sfu = sub.add_parser(
        "sftp.user", help="manage an SFTP user-store file")
    sfu.add_argument("-store", required=True)
    sfu.add_argument("action", choices=["add", "delete", "list"])
    sfu.add_argument("-name", default="")
    sfu.add_argument("-password", default="")
    sfu.add_argument("-home", default="")
    sfu.add_argument("-pubkey", default="",
                     help="authorized key line 'ssh-ed25519 <b64>'")
    sfu.add_argument("-perm", action="append", default=[],
                     help="path:perm1,perm2 (repeatable)")

    rsync = sub.add_parser(
        "filer.remote.sync", help="push local changes under a "
        "remote-mounted directory back to the foreign object store "
        "(command/filer_remote_sync.go)")
    rsync.add_argument("-filer", required=True)
    rsync.add_argument("-dir", required=True,
                       help="remote-mounted filer directory")
    rsync.add_argument("-state", default="",
                       help="offset checkpoint file")
    rsync.add_argument("-interval", type=float, default=0.5)

    sh = sub.add_parser("shell", help="interactive admin shell")
    sh.add_argument("-master", default="127.0.0.1:9333")
    sh.add_argument("-filer", default="",
                    help="filer host:port for the fs.* command family")
    sh.add_argument("command", nargs="*",
                    help="run one command and exit")

    bm = sub.add_parser("benchmark",
                        help="write/read load test (weed benchmark)")
    bm.add_argument("-master", default="127.0.0.1:9333")
    bm.add_argument("-n", type=int, default=1000)
    bm.add_argument("-size", type=int, default=1024)
    bm.add_argument("-c", type=int, default=16)

    crt = sub.add_parser("cert", help="mint a cluster PKI (CA + node "
                         "cert) for the TLS plane (security/tls.go)")
    crt.add_argument("-dir", default="certs")
    crt.add_argument("-hosts", default="127.0.0.1,localhost",
                     help="comma-separated SAN hosts/IPs")

    sc = sub.add_parser("scaffold", help="print a commented template "
                        "config (command/scaffold)")
    sc.add_argument("-config", default="security",
                    choices=["security", "filer", "notification",
                             "replication"],
                    help="which template to print")

    up = sub.add_parser("upload", help="upload a file")
    up.add_argument("-master", default="127.0.0.1:9333")
    up.add_argument("file")

    an = sub.add_parser(
        "analyze", help="project-native static analysis: SWFS rules + "
        "baseline (devtools/RULES.md)")
    an.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: the "
                         "seaweedfs_tpu package)")
    an.add_argument("-json", dest="json_out", action="store_true",
                    help="machine-readable findings")
    an.add_argument("-baseline", default="",
                    help="baseline file (default: "
                         "devtools/baseline.json)")
    an.add_argument("-writeBaseline", dest="write_baseline",
                    action="store_true",
                    help="accept all current findings into the "
                         "baseline and exit 0")
    an.add_argument("-noBaseline", dest="no_baseline",
                    action="store_true",
                    help="report every finding, baselined or not")
    an.add_argument("-rules", default="",
                    help="comma-separated rule ids to run "
                         "(default: all)")

    sub.add_parser("version", help="print the build version "
                   "(command/version.go)")

    mt = sub.add_parser(
        "filer.meta.tail", help="tail the filer metadata event "
        "stream as JSON lines (command/filer_meta_tail.go)")
    mt.add_argument("-filer", default="127.0.0.1:8888")
    mt.add_argument("-sinceNs", dest="since_ns", type=int, default=0,
                    help="replay from this event timestamp (0 = now)")
    mt.add_argument("-pathPrefix", dest="path_prefix", default="",
                    help="only events under this path")
    mt.add_argument("-interval", type=float, default=1.0)
    mt.add_argument("-once", action="store_true",
                    help="drain the backlog and exit (no follow)")

    # offline volume tools (weed fix / compact / export): run against
    # UNMOUNTED volume files — stop the volume server first
    fx = sub.add_parser("fix", help="recreate a volume's .idx by "
                        "scanning its .dat (command/fix.go; stop the "
                        "volume server first)")
    fx.add_argument("-dir", required=True)
    fx.add_argument("-volumeId", dest="volume_id", type=int,
                    required=True)
    fx.add_argument("-collection", default="")

    cp = sub.add_parser("compact", help="offline vacuum of a volume "
                        "file (command/compact.go; stop the volume "
                        "server first)")
    cp.add_argument("-dir", required=True)
    cp.add_argument("-volumeId", dest="volume_id", type=int,
                    required=True)
    cp.add_argument("-collection", default="")

    ex = sub.add_parser("export", help="list or tar the live files "
                        "of one volume (command/export.go)")
    ex.add_argument("-dir", required=True)
    ex.add_argument("-volumeId", dest="volume_id", type=int,
                    required=True)
    ex.add_argument("-collection", default="")
    ex.add_argument("-o", dest="out", default="",
                    help="output .tar path (omit to just list)")

    down = sub.add_parser("download", help="download a fid")
    down.add_argument("-master", default="127.0.0.1:9333")
    down.add_argument("fid")

    # WEED_<ROLE>_<FLAG> env-var override layer (util/config,
    # reference viper SetEnvPrefix("weed")): rewrites parser DEFAULTS,
    # so explicit command-line flags still win
    from .util.config import apply_env_defaults
    env_applied = apply_env_defaults(sub.choices)

    args = p.parse_args(argv)

    from .util import wlog
    if args.v is not None:
        wlog.set_verbosity(args.v)
    if args.log_json:
        wlog.json_format(True)
    if args.logdir:
        import os as _os
        _os.makedirs(args.logdir, exist_ok=True)
        wlog.set_output(_os.path.join(args.logdir, "weed.log"))
    for line in env_applied:
        wlog.info("env override: %s", line, component="config")

    if args.securityToml:
        from . import qos, security
        security.configure(security.load_security_toml(args.securityToml))
        # the same file may carry a [qos] section (qos.py): tenant
        # admission limits + the foreground SLO for the EC throttle
        qos_cfg = qos.load_qos_toml(args.securityToml)
        if qos_cfg is not None:
            qos.configure(qos_cfg)
            wlog.info("qos config loaded from %s", args.securityToml,
                      component="config")

    if args.cmd == "master":
        from .server.master_server import MasterServer
        ms = MasterServer(args.ip, args.port,
                          volume_size_limit_mb=args.volumeSizeLimitMB,
                          default_replication=args.defaultReplication,
                          peers=args.peers or None,
                          meta_dir=args.mdir or None)
        ms.start()
        if args.metrics_address:
            from .stats import MetricsPusher
            MetricsPusher(ms.metrics, "master", ms.url,
                          args.metrics_address,
                          args.metrics_interval).start()
            print(f"pushing metrics to {args.metrics_address} "
                  f"every {args.metrics_interval}s")
        if args.telemetry and args.telemetry_url:
            from .telemetry import TelemetryClient
            TelemetryClient(args.telemetry_url,
                            enabled=True).start(ms.url)
            print(f"telemetry enabled -> {args.telemetry_url}")
        print(f"master listening on {ms.url}")
        _wait()
    elif args.cmd == "volume":
        from .server.volume_server import VolumeServer
        if args.tierBackend:
            from .storage.backend import configure_s3_backend
            parts = args.tierBackend.split(",")
            configure_s3_backend("default", parts[0],
                                 parts[1] if len(parts) > 1 else "tier",
                                 parts[2] if len(parts) > 2 else "",
                                 parts[3] if len(parts) > 3 else "")
        if args.mmap_mb:
            from .storage import store as _store_mod
            _store_mod.MMAP_READ_MB = args.mmap_mb
        vs = VolumeServer(args.dir.split(","), args.mserver,
                          host=args.ip, port=args.port,
                          max_volume_count=args.max,
                          data_center=args.dataCenter, rack=args.rack,
                          fsync=args.fsync)
        vs.start()
        if args.metrics_address:
            from .stats import MetricsPusher
            MetricsPusher(vs.metrics, "volume_server", vs.url,
                          args.metrics_address,
                          args.metrics_interval).start()
            print(f"pushing metrics to {args.metrics_address}")
        print(f"volume server listening on {vs.url}")
        _wait()
    elif args.cmd == "server":
        import os as _os
        from .server.master_server import MasterServer
        from .server.volume_server import VolumeServer
        if args.tierBackend:
            from .storage.backend import configure_s3_backend
            parts = args.tierBackend.split(",")
            configure_s3_backend("default", parts[0],
                                 parts[1] if len(parts) > 1 else "tier",
                                 parts[2] if len(parts) > 2 else "",
                                 parts[3] if len(parts) > 3 else "")
        ms = MasterServer(args.ip, args.master_port).start()
        vs = VolumeServer([args.dir], ms.url, host=args.ip,
                          port=args.volume_port).start()
        print(f"master on {ms.url}, volume on {vs.url}")
        if args.filer or args.s3:
            from .server.filer_server import FilerServer
            fs = FilerServer(
                ms.url, args.ip, args.filer_port,
                store_path=_os.path.join(args.dir, "filer.db"))
            fs.start()
            print(f"filer on {fs.url}")
            if args.s3:
                from .s3 import S3ApiServer
                creds = {args.s3_access: args.s3_secret} \
                    if args.s3_access else None
                gw = S3ApiServer(fs.filer, args.ip, args.s3_port,
                                 credentials=creds).start()
                print(f"s3 on {gw.url}")
        _wait()
    elif args.cmd == "filer":
        from .server.filer_server import FilerServer
        from .util.config import (filer_store_from_toml, find_toml,
                                  notification_from_toml)
        store_type, store_path = args.store_type, args.store
        # scaffold TOMLs override FLAG DEFAULTS only: an explicit
        # -store/-storeType on the command line wins (viper layering)
        toml_path = find_toml("filer.toml")
        if toml_path and store_type == "sqlite" and \
                store_path == "filer.db":
            picked = filer_store_from_toml(toml_path)
            if picked:
                store_type, store_path = picked
                wlog.info("filer store from %s: %s %s", toml_path,
                          store_type, store_path, component="config")
        notification = args.notification
        ntoml = find_toml("notification.toml")
        if ntoml and not notification:
            notification = notification_from_toml(ntoml)
            if notification:
                wlog.info("notification from %s: %s", ntoml,
                          notification, component="config")
        if args.meta_plane:
            # via the environment so spawned -workers siblings (which
            # re-exec this argv minus -port/-workers) inherit the same
            # plane mode even when driven by the flag
            os.environ["SEAWEEDFS_TPU_FILER_META_PLANE"] = \
                args.meta_plane
        workers = args.workers
        if workers is None:
            try:
                workers = int(os.environ.get(
                    "SEAWEEDFS_TPU_FILER_WORKERS", "") or 1)
            except ValueError:
                workers = 1
        is_worker = workers == 0          # spawned sibling (internal)
        if workers > 1 and store_type != "sqlite":
            wlog.warning("filer -workers needs the sqlite store "
                         "(shared WAL + metalog); running 1 process",
                         component="filer")
            workers = 1
        fs = FilerServer(args.master, args.ip, args.port,
                         store_path=store_path,
                         collection=args.collection,
                         replication=args.replication,
                         store_type=store_type,
                         notification=notification,
                         lock_peers=[p.strip() for p in
                                     args.lock_peers.split(",")
                                     if p.strip()],
                         reuse_port=is_worker or workers > 1)
        fs.start()
        worker_procs: list = []
        if is_worker:
            # exit when orphaned: the parent (or the harness that
            # killed it) is gone, so this listener must die too
            import threading as _threading

            def _orphan_watch(ppid: int = os.getppid()):
                while True:
                    time.sleep(1.0)
                    if os.getppid() != ppid:
                        os._exit(0)
            _threading.Thread(target=_orphan_watch,
                              daemon=True).start()
        elif workers > 1:
            # pre-fork: N-1 sibling processes re-exec this command on
            # the RESOLVED port with SO_REUSEPORT; the kernel spreads
            # connections across the workers' accept queues
            import subprocess as _subprocess
            # any ONE worker's /metrics scrape must report the whole
            # fleet's process-tree CPU/RSS (stats._proc_tree_sample):
            # siblings inherit this env and root their /proc walk at
            # the pre-fork parent instead of themselves
            os.environ["SEAWEEDFS_TPU_TREE_ROOT"] = str(os.getpid())
            argv = []
            skip = False
            for a in sys.argv[1:]:
                if skip:
                    skip = False
                    continue
                if a in ("-port", "-workers"):
                    skip = True
                    continue
                argv.append(a)
            argv += ["-port", str(fs.http.port), "-workers", "0"]
            for _ in range(workers - 1):
                worker_procs.append(_subprocess.Popen(
                    [sys.executable, "-m", "seaweedfs_tpu"] + argv))
            print(f"filer pre-forked {workers - 1} sibling workers "
                  f"on port {fs.http.port}")
            # monitor: a crashed worker is reaped, logged, and
            # respawned (bounded — a worker that cannot stay up must
            # not become a fork loop); without this the filer would
            # silently serve with fewer processes than -workers asked
            import threading as _threading
            respawns = [0]
            drained: "set[int]" = set()   # pids the autopilot drained
            # on purpose — the monitor must not resurrect them

            def _worker_monitor():
                while True:
                    time.sleep(2.0)
                    for i, wp in enumerate(worker_procs):
                        rc = wp.poll()
                        if rc is None or wp.pid in drained:
                            continue
                        wlog.warning(
                            f"filer worker pid={wp.pid} exited "
                            f"rc={rc}", component="filer")
                        if respawns[0] >= 20:
                            continue
                        respawns[0] += 1
                        worker_procs[i] = _subprocess.Popen(
                            [sys.executable, "-m", "seaweedfs_tpu"]
                            + argv)
            _threading.Thread(target=_worker_monitor,
                              daemon=True).start()
            # SLO autopilot "workers" actuator (autopilot.py, ISSUE
            # 20): only the pre-fork PARENT registers it — it owns
            # the sibling fleet — so a single-process filer can never
            # have workers conjured by a control rule.  Fleet size
            # counts the parent; bounds [1, 2x the requested size].
            ap = getattr(fs, "autopilot", None)
            if ap is not None:
                from .autopilot import Actuator
                _wlock = _threading.Lock()

                def _fleet_size() -> float:
                    with _wlock:
                        return 1.0 + sum(
                            1 for wp in worker_procs
                            if wp.poll() is None
                            and wp.pid not in drained)

                def _scale_fleet(n: float) -> None:
                    want = max(0, int(round(n)) - 1)
                    with _wlock:
                        live = [wp for wp in worker_procs
                                if wp.poll() is None
                                and wp.pid not in drained]
                        while len(live) < want:
                            wp = _subprocess.Popen(
                                [sys.executable, "-m",
                                 "seaweedfs_tpu"] + argv)
                            worker_procs.append(wp)
                            live.append(wp)
                        while len(live) > want:
                            wp = live.pop()
                            drained.add(wp.pid)
                            wp.terminate()

                ap.register(Actuator(
                    "workers", get=_fleet_size, set=_scale_fleet,
                    lo=1.0, hi=float(max(workers * 2, 2)),
                    cooldown=30.0,
                    describe="SO_REUSEPORT pre-fork filer "
                             "processes (parent included)"))
        if args.metrics_address:
            from .stats import MetricsPusher
            MetricsPusher(fs.metrics, "filer", fs.url,
                          args.metrics_address,
                          args.metrics_interval).start()
            print(f"pushing metrics to {args.metrics_address} "
                  f"every {args.metrics_interval}s")
        print(f"filer listening on {fs.url}")
        _wait()
    elif args.cmd == "s3":
        from .s3 import S3ApiServer
        from .filer import Filer
        from .filer.filer_store import SqliteStore
        creds = {args.accessKey: args.secretKey} if args.accessKey \
            else None
        iam_store = sts = kms = None
        if args.iam_config:
            from .iam import IdentityStore
            iam_store = IdentityStore(args.iam_config)
        if args.sts_key:
            from .iam import StsService
            from .iam.sts import RoleStore
            sts = StsService(args.sts_key,
                             RoleStore(args.roles_file or None))
        if args.kms_cloud:
            from .iam import kms_cloud
            kind, _, rest = args.kms_cloud.partition(":")
            parts = rest.split(",")
            ctor = {"gcp": kms_cloud.GcpKms,
                    "azure": kms_cloud.AzureKms,
                    "openbao": kms_cloud.OpenBaoKms}.get(kind)
            if ctor is None:
                print(f"unknown -kmsCloud provider {kind!r}",
                      file=sys.stderr)
                return 2
            kms = ctor(parts[0],
                       parts[1] if len(parts) > 1 else "",
                       token=parts[2] if len(parts) > 2 else "")
        elif args.kms_endpoint:
            from .iam.kms_aws import AwsKms
            parts = args.kms_endpoint.split(",")
            kms = AwsKms(parts[0],
                         parts[1] if len(parts) > 1 else "",
                         parts[2] if len(parts) > 2 else "",
                         parts[3] if len(parts) > 3 else "us-east-1")
        elif args.kms_file:
            from .iam.kms import LocalKms
            kms = LocalKms(args.kms_file)
        if args.filer:
            from .filer.client import FilerClient
            backend = FilerClient(args.filer)
        else:
            backend = Filer(args.master, SqliteStore(args.store))
        gw = S3ApiServer(backend, args.ip, args.port,
                         credentials=creds,
                         iam=iam_store, sts=sts, kms=kms,
                         metrics_port=args.metrics_port)
        gw.start()
        if args.metrics_address:
            from .stats import MetricsPusher
            MetricsPusher(gw.metrics, "s3", gw.url,
                          args.metrics_address,
                          args.metrics_interval).start()
            print(f"pushing metrics to {args.metrics_address} "
                  f"every {args.metrics_interval}s")
        print(f"s3 gateway listening on {gw.url}" +
              (f" (filer {args.filer})" if args.filer else "") +
              (f" (metrics {gw.metrics_http.url}/metrics)"
               if gw.metrics_http is not None else ""))
        _wait()
    elif args.cmd == "iam":
        from .iam import IdentityStore, StsService
        from .iam.iamapi import IamApiServer
        from .iam.sts import RoleStore
        store = IdentityStore(args.iam_config)
        sts = StsService(args.sts_key,
                         RoleStore(args.roles_file or None)) \
            if args.sts_key else None
        if args.oidc_config and sts is None:
            p.error("-oidcConfig requires -stsKey (web identities "
                    "mint STS credentials)")
        if sts is not None and args.oidc_config:
            import json as _json
            from .iam.oidc import OidcProvider
            with open(args.oidc_config) as f:
                for cfg in _json.load(f):
                    pems = []
                    if cfg.get("rsaPublicKeyFile"):
                        with open(cfg["rsaPublicKeyFile"],
                                  "rb") as kf:
                            pems.append(kf.read())
                    sts.add_provider(OidcProvider(
                        cfg["name"], cfg["issuer"],
                        cfg.get("audience", ""),
                        rsa_public_keys_pem=pems,
                        hs256_secret=cfg.get("hs256Secret", "")))
                    print(f"oidc provider {cfg['name']} "
                          f"({cfg['issuer']})")
        srv = IamApiServer(store, sts, args.ip, args.port).start()
        print(f"iam api on {srv.url}")
        _wait()
    elif args.cmd == "admin":
        from .plugin.admin import AdminServer
        ad = AdminServer(args.master, args.ip, args.port,
                         detection_interval=args.detectionInterval,
                         data_dir=args.dataDir or None)
        ad.start()
        print(f"admin listening on {ad.url}")
        _wait()
    elif args.cmd == "worker":
        from .plugin.handlers import (EcBalanceHandler,
                                      EcEncodeHandler,
                                      EcRebuildHandler,
                                      VacuumHandler,
                                      VolumeBalanceHandler)
        from .plugin.worker import PluginWorker
        handlers = []
        caps = args.capabilities.split(",")
        if "erasure_coding" in caps or "ec" in caps:
            if args.backend in ("", "jax"):
                # the one role that owns the accelerator: claim it
                # before registering, so a missing chip stops the
                # worker here instead of failing its first job, and
                # backend init happens outside any job's liveness
                # window
                from .storage.erasure_coding import ec_context
                dev = ec_context.own_device()
                print(f"worker owns {dev['platform']} {dev['kind']} "
                      f"x{dev['count']}, compile cache "
                      f"{ec_context.compile_cache_dir()}")
            handlers.append(EcEncodeHandler(
                backend=args.backend or None))
        if "erasure_coding" in caps or "ec" in caps or \
                "ec_rebuild" in caps:
            handlers.append(EcRebuildHandler())
        if "vacuum" in caps:
            handlers.append(VacuumHandler())
        if "volume_balance" in caps or "balance" in caps:
            handlers.append(VolumeBalanceHandler())
        if "ec_balance" in caps:
            handlers.append(EcBalanceHandler())
        w = PluginWorker(args.admin, args.master, args.dir, handlers)
        w.start()
        print(f"worker {w.worker_id} polling {args.admin}")
        _wait()
    elif args.cmd == "webdav":
        # attach to the RUNNING filer's namespace (the reference's
        # weed webdav -filer), not a private store
        from .filer.client import FilerClient
        from .server.webdav_server import WebDavServer
        dav = WebDavServer("", FilerClient(args.filer), args.ip,
                           args.port).start()
        print(f"webdav on {dav.url} serving filer {args.filer}")
        _wait()
    elif args.cmd == "mount":
        from .mount.fuse_ctypes import mount as fuse_mount
        print(f"mounting filer {args.filer} at {args.dir} (read-only)")
        return fuse_mount(args.filer, args.dir)
    elif args.cmd == "mq.broker":
        import signal
        from .mq import BrokerServer
        br = BrokerServer(args.filer, args.ip, args.port).start()
        # graceful SIGTERM: drain hot buffers to the filer before exit
        signal.signal(signal.SIGTERM,
                      lambda *_: (br.stop(), sys.exit(0)))
        print(f"mq broker on {br.url} (filer {args.filer})")
        try:
            _wait()
        finally:
            br.stop()
    elif args.cmd == "mq.agent":
        from .mq.agent import AgentServer
        ag = AgentServer(args.broker, args.ip, args.port).start()
        print(f"mq agent on {ag.url} -> broker {args.broker}")
        _wait()
    elif args.cmd == "mq.kafka":
        from .mq.kafka_gateway import KafkaGateway
        users = None
        if args.users:
            entries = [u for u in args.users.split(",") if u]
            bad = [u for u in entries if ":" not in u]
            if bad or not entries:
                # an operator who ASKED for auth must never get an
                # open gateway because of a typo'd separator
                p.error(f"-users: malformed credential(s) "
                        f"{bad or args.users!r} (want user:pass"
                        f"[,user2:pass2])")
            users = dict(u.split(":", 1) for u in entries)
        gw = KafkaGateway(args.broker, args.ip, args.port,
                          users=users).start()
        print(f"kafka gateway on {args.ip}:{gw.port} over broker "
              f"{args.broker}" +
              (" (SASL/PLAIN required)" if users else ""))
        _wait()
    elif args.cmd == "filer.sync":
        from .filer.filer_sync import FilerSync
        syncer = FilerSync(args.sync_from, args.sync_to,
                           args.state or None,
                           poll_interval=args.interval)
        print(f"filer.sync {args.sync_from} -> {args.sync_to} "
              f"(offset state: {syncer.state_path})")
        try:
            syncer.run()
        except KeyboardInterrupt:
            pass
    elif args.cmd == "filer.backup.s3":
        from .filer.s3_sink import S3Sink
        sink = S3Sink(args.filer, args.endpoint, args.bucket,
                      args.access_key, args.secret_key, args.prefix,
                      args.state or None, poll_interval=args.interval)
        print(f"filer.backup.s3 {args.filer} -> "
              f"{args.endpoint}/{args.bucket}/{args.prefix} "
              f"(offset state: {sink.state_path})")
        try:
            sink.run()
        except KeyboardInterrupt:
            pass
    elif args.cmd == "filer.backup.gcs":
        from .filer.cloud_sinks import GcsSink
        sink = GcsSink(args.filer, args.bucket, args.endpoint,
                       args.token, args.prefix, args.state or None,
                       poll_interval=args.interval)
        print(f"filer.backup.gcs {args.filer} -> "
              f"{args.endpoint}/{args.bucket}/{args.prefix}")
        try:
            sink.run()
        except KeyboardInterrupt:
            pass
    elif args.cmd == "filer.backup.azure":
        from .filer.cloud_sinks import AzureSink
        sink = AzureSink(args.filer, args.account, args.account_key,
                         args.container, args.endpoint, args.prefix,
                         args.state or None,
                         poll_interval=args.interval)
        print(f"filer.backup.azure {args.filer} -> "
              f"{sink.endpoint}/{args.container}/{args.prefix}")
        try:
            sink.run()
        except KeyboardInterrupt:
            pass
    elif args.cmd == "filer.backup.b2":
        from .filer.cloud_sinks import B2Sink
        sink = B2Sink(args.filer, args.key_id, args.app_key,
                      args.bucket, endpoint=args.endpoint,
                      key_prefix=args.prefix,
                      state_path=args.state or None,
                      poll_interval=args.interval)
        print(f"filer.backup.b2 {args.filer} -> b2://{args.bucket}/"
              f"{args.prefix}")
        try:
            sink.run()
        except KeyboardInterrupt:
            pass
    elif args.cmd == "filer.backup":
        from .filer.filer_backup import FilerBackup
        bak = FilerBackup(args.filer, args.dir, args.state or None,
                          poll_interval=args.interval)
        print(f"filer.backup {args.filer} -> {args.dir} "
              f"(offset state: {bak.state_path})")
        try:
            bak.run()
        except KeyboardInterrupt:
            pass
    elif args.cmd == "filer.remote.sync":
        from .remote import RemoteSyncer
        syncer = RemoteSyncer(args.filer, args.dir,
                              args.state or None,
                              args.interval).start()
        print(f"remote-syncing {args.dir} on {args.filer}")
        try:
            _wait()
        finally:
            syncer.stop()
    elif args.cmd == "sftp":
        from cryptography.hazmat.primitives import serialization
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey)
        from .filer.client import FilerClient
        from .sftp import SftpService, UserStore
        key = None
        if args.host_key:
            if os.path.exists(args.host_key):
                with open(args.host_key, "rb") as f:
                    key = serialization.load_pem_private_key(
                        f.read(), password=None)
            else:
                key = Ed25519PrivateKey.generate()
                with open(args.host_key, "wb") as f:
                    f.write(key.private_bytes(
                        serialization.Encoding.PEM,
                        serialization.PrivateFormat.PKCS8,
                        serialization.NoEncryption()))
        ldap = None
        if args.ldap_server:
            from .iam.ldap import LdapProvider
            host, _, port = args.ldap_server.partition(":")
            default_port = 636 if args.ldap_tls else 389
            ldap = LdapProvider(
                host, int(port or default_port),
                base_dn=args.ldap_base_dn,
                user_dn_template=args.ldap_dn_template,
                bind_dn=args.ldap_bind_dn,
                bind_password=args.ldap_bind_password,
                use_tls=args.ldap_tls)
        svc = SftpService(
            FilerClient(args.filer), UserStore(args.user_store),
            host_key=key, port=args.port,
            auth_methods=tuple(args.auth_methods.split(",")),
            banner=args.banner, ldap=ldap).start()
        print(f"sftp on {args.ip}:{svc.port} serving filer "
              f"{args.filer}")
        _wait()
    elif args.cmd == "sftp.user":
        from .sftp import User, UserStore
        store = UserStore(args.store)
        if args.action == "list":
            for u in store:
                print(f"{u.username} home={u.home_dir} "
                      f"keys={len(u.public_keys)} "
                      f"perms={u.permissions}")
        elif args.action == "delete":
            store.delete(args.name)
            print(f"deleted {args.name}")
        else:
            u = store.get(args.name) or User(args.name, args.home)
            if args.home:
                u.home_dir = args.home
            if args.password:
                u.set_password(args.password)
            if args.pubkey:
                u.add_public_key(args.pubkey)
            for spec in args.perm:
                path, _, perms = spec.partition(":")
                u.permissions[path] = perms.split(",")
            store.put(u)
            print(f"saved {u.username}")
    elif args.cmd == "shell":
        from .shell import CommandEnv, run_command
        env = CommandEnv(args.master, filer=args.filer)
        if args.command:
            # ';'-separated sequences share one env (so `lock;
            # volume.move ...; unlock` works as a one-shot)
            for one in " ".join(args.command).split(";"):
                if one.strip():
                    print(run_command(env, one.strip()))
            return 0
        _repl(env)
    elif args.cmd == "benchmark":
        import json as _json
        from .benchmark import run_benchmark
        for r in run_benchmark(args.master, args.n, args.size, args.c):
            print(_json.dumps(r))
    elif args.cmd == "cert":
        from .tls import generate_cluster_certs
        paths = generate_cluster_certs(
            args.dir, [h.strip() for h in args.hosts.split(",")
                       if h.strip()])
        print(f"wrote {paths['ca']}, {paths['cert']}, {paths['key']}")
        print("enable via security.toml:\n[tls]\n"
              f'ca = "{paths["ca"]}"\ncert = "{paths["cert"]}"\n'
              f'key = "{paths["key"]}"\nmtls = true')
    elif args.cmd == "scaffold" and args.config == "filer":
        # command/scaffold/filer.toml shape (util/config.py
        # filer_store_from_toml reads the enabled section)
        print("""\
# filer.toml — place in ./, ~/.seaweedfs/, or /etc/seaweedfs/
# the first ENABLED section picks the filer's metadata store
# (command/scaffold/filer.toml layout; archetype mapping in
# seaweedfs_tpu/util/config.py)

[sqlite]
enabled = true
dbFile = "filer.db"           # or ":memory:"

[leveldb2]
# embedded ordered-KV (our LSM store — the reference's default)
enabled = false
dir = "./filerldb2"

[redis2]
# any RESP2 server (hand-rolled client, filer/redis_store.py)
enabled = false
address = "localhost:6379"

[elastic7]
# any ES-wire JSON-HTTP server (filer/elastic_store.py)
enabled = false
servers = ["http://localhost:9200"]""")
    elif args.cmd == "scaffold" and args.config == "notification":
        print("""\
# notification.toml — metadata-event publishing
# (command/scaffold/notification.toml layout; the first enabled
# sink becomes the filer's -notification spec)

[notification.webhook]
enabled = false
url = "http://localhost:9000/events"

[notification.kafka]
enabled = false
hosts = ["localhost:9092"]
topic = "seaweedfs_meta"

[notification.log]
enabled = false
path = "filer_events.log"

[notification.mq]
enabled = false
broker = "localhost:17777"
namespace = "notifications"
topic = "filer_meta"\
""")
    elif args.cmd == "scaffold" and args.config == "replication":
        print("""\
# replication.toml — filer.backup sink selection
# (command/scaffold/replication.toml layout; the first enabled
# [sink.*] section drives filer.backup)

[sink.local]
enabled = false
directory = "/backup"

[sink.s3]
enabled = false
endpoint = "localhost:8333"
bucket = "backup"
aws_access_key_id = ""
aws_secret_access_key = ""

[sink.gcs]
enabled = false
bucket = "backup"

[sink.azure]
enabled = false
container = "backup"

[sink.backblaze]
enabled = false
bucket = "backup"\
""")
    elif args.cmd == "scaffold":
        # command/scaffold/security.toml layout (keys match
        # util/config.go:34 LoadSecurityConfiguration)
        print("""\
# security.toml — place beside the binary or pass -securityToml
# (command/scaffold/security.toml layout)

[jwt.signing]
# per-fid write tokens minted by the master on assign
key = ""
expires_after_seconds = 10

[jwt.signing.read]
# optional read-token gate on the volume data path
key = ""
expires_after_seconds = 10

[admin]
# admin-plane key: guards /admin/*, raft, heartbeat, grow, lock
key = ""

[access]
# CIDR whitelist for unauthenticated access (empty = no whitelist)
white_list = []

# [tls]
# cluster-wide TLS/mTLS (security/tls.go; mint a PKI with
# `python -m seaweedfs_tpu cert -dir certs`)
# ca = "certs/ca.crt"
# cert = "certs/node.crt"
# key = "certs/node.key"
# mtls = true

# [qos]
# per-tenant admission + background EC throttle (qos.py); runtime
# lever: POST /debug/qos on any role
# enabled = true
# slo_p99_ms = 200          # foreground p99 SLO for the EC throttle
# [qos.default]             # any tenant without an override
# rps = 200
# burst = 400
# inflight_mb = 64
# [qos.tenants.AKIDEXAMPLE] # per-access-key override
# rps = 10
# burst = 10""")
    elif args.cmd == "upload":
        from . import operation
        with open(args.file, "rb") as f:
            data = f.read()
        fid = operation.submit(args.master, data, name=args.file)
        print(fid)
    elif args.cmd == "analyze":
        from .devtools.analyze import run_cli
        return run_cli(args.paths, json_out=args.json_out,
                       baseline_path=args.baseline,
                       write_baseline=args.write_baseline,
                       no_baseline=args.no_baseline,
                       rule_ids=args.rules)
    elif args.cmd == "version":
        from . import __version__
        print(f"seaweedfs-tpu {__version__} "
              f"(python {sys.version.split()[0]})")
    elif args.cmd == "filer.meta.tail":
        # command/filer_meta_tail.go: follow the metadata log from a
        # timestamp, one JSON event per line; -once drains and exits
        import json as _json

        from .server.httpd import http_json
        since = args.since_ns
        if since == 0 and not args.once:
            import time as _t
            since = _t.time_ns()          # "now": only new events
        try:
            while True:
                try:
                    r = http_json(
                        "GET", f"{args.filer}/__meta__/events?"
                               f"sinceNs={since}&limit=1000")
                except OSError as e:
                    # follow mode must survive a filer restart /
                    # network blip (FilerSync retries the same way);
                    # -once surfaces the failure instead
                    if args.once:
                        print(f"filer.meta.tail: {e}",
                              file=sys.stderr)
                        return 1
                    print(f"filer.meta.tail: {e}; retrying",
                          file=sys.stderr)
                    time.sleep(args.interval)
                    continue
                if "error" in r:
                    # a 401/404 must not read as "log is empty"
                    print(f"filer.meta.tail: {r['error']}",
                          file=sys.stderr)
                    return 1
                for ev in r.get("events", []):
                    path = (ev.get("newEntry") or
                            ev.get("oldEntry") or {}).get(
                                "fullPath", "")
                    if args.path_prefix and \
                            not path.startswith(args.path_prefix):
                        since = max(since, int(ev.get("tsNs", 0)))
                        continue
                    print(_json.dumps(ev), flush=True)
                    since = max(since, int(ev.get("tsNs", 0)))
                if args.once and len(r.get("events", [])) < 1000:
                    break
                if len(r.get("events", [])) < 1000:
                    time.sleep(args.interval)
        except KeyboardInterrupt:
            pass
    elif args.cmd == "fix":
        # command/fix.go: replay the .dat sequentially into a fresh
        # .idx (writes -> put, tombstones -> delete-row), exactly the
        # recovery the reference runs on index corruption
        import os as _os

        from .storage import idx as idxmod
        from .storage import types as stypes
        from .storage.volume import walk_dat
        dat = _offline_vol_path(args, ".dat")
        idx_path = _offline_vol_path(args, ".idx")
        if not _os.path.exists(dat):
            print(f"no {dat}", file=sys.stderr)
            return 1
        tmp = idx_path + ".fix"
        n_writes = n_dels = 0
        with open(tmp, "wb") as f:
            for needle, off in walk_dat(dat):
                if needle.data:
                    f.write(idxmod.entry_bytes(
                        needle.id, stypes.to_stored_offset(off),
                        needle.size))
                    n_writes += 1
                else:
                    f.write(idxmod.entry_bytes(
                        needle.id, 0, stypes.TOMBSTONE_FILE_SIZE))
                    n_dels += 1
        _os.replace(tmp, idx_path)
        print(f"fixed {idx_path}: {n_writes} writes, "
              f"{n_dels} tombstones")
    elif args.cmd == "compact":
        # command/compact.go: offline shadow-compact + commit on an
        # unmounted volume
        import os as _os

        from .storage.volume import Volume
        if not _os.path.exists(_offline_vol_path(args, ".dat")):
            # Volume() would CREATE an empty volume here — a typo'd
            # id must fail, not mint stray files the server later
            # serves as a real volume
            print(f"no {_offline_vol_path(args, '.dat')}",
                  file=sys.stderr)
            return 1
        v = Volume(args.dir, args.volume_id,
                   collection=args.collection)
        before = v.dat_size()
        garbage = v.garbage_level()
        v.vacuum()
        after = v.dat_size()
        v.close()
        print(f"compacted volume {args.volume_id}: {before} -> "
              f"{after} bytes (garbage was {garbage:.0%})")
    elif args.cmd == "export":
        # command/export.go: list live needles, or tar their payloads
        # (member names <key-hex>[_<name>])
        import os as _os
        import tarfile

        from .storage.volume import Volume
        if not _os.path.exists(_offline_vol_path(args, ".dat")):
            print(f"no {_offline_vol_path(args, '.dat')}",
                  file=sys.stderr)
            return 1
        v = Volume(args.dir, args.volume_id,
                   collection=args.collection)
        entries = sorted(v.nm.items())
        tar = tarfile.open(args.out, "w") if args.out else None
        count = 0
        for key, stored_off, size in entries:
            n = v._read_at(stored_off, size)
            fname = f"{key:x}"
            if n.has_name():
                fname += "_" + n.name.decode("utf-8", "replace")
            if tar is None:
                mime = n.mime.decode("utf-8", "replace") \
                    if n.has_mime() else "-"
                print(f"{fname}\t{len(n.data)}\t{mime}")
            else:
                import io as _io
                info = tarfile.TarInfo(fname)
                info.size = len(n.data)
                info.mtime = n.last_modified or 0
                tar.addfile(info, _io.BytesIO(n.data))
            count += 1
        if tar is not None:
            tar.close()
            print(f"exported {count} files to {args.out}")
        else:
            print(f"{count} live files in volume {args.volume_id}")
        v.close()
    elif args.cmd == "download":
        from . import operation
        sys.stdout.buffer.write(operation.read(args.master, args.fid))
    return 0


def _offline_vol_path(args, ext: str) -> str:
    """<dir>/<collection_>_?<vid><ext> — the volume.file_name naming
    rule, shared by the offline fix/compact/export tools."""
    import os as _os
    name = (f"{args.collection}_" if args.collection else "") + \
        f"{args.volume_id}{ext}"
    return _os.path.join(args.dir, name)


def _repl(env) -> None:
    from .shell import run_command
    while True:
        try:
            line = input("> ").strip()
        except (EOFError, KeyboardInterrupt):
            break
        if line in ("exit", "quit"):
            break
        if not line:
            continue
        try:
            print(run_command(env, line))
        except Exception as e:  # noqa: BLE001 — REPL must survive
            print(f"error: {e}")


def _wait() -> None:
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    sys.exit(main())
