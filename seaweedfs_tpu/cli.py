"""Command-line entry point — the `weed` binary equivalent.

Mirrors /root/reference/weed/weed.go:48 + command/command.go:11-45:
one binary, subcommand dispatch. Run as `python -m seaweedfs_tpu <cmd>`.

Subcommands: master, volume, server (combined), shell, benchmark,
upload, download, filer, s3, version.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from .rpc.httpclient import session


def _ssl_ctx(args):
    """Build the server SSLContext from -security (None = plain HTTP).
    Applied to control-plane/gateway listeners (master, follower,
    filer, s3, webdav, iam, mq); the volume HTTP data path stays
    plain like the reference's (tls.go wraps gRPC, not the blob
    HTTP port)."""
    path = getattr(args, "security", "")
    if not path:
        return None
    from .utils.tls import context_from_config, load_security_config

    return context_from_config(load_security_config(path))


def _add_commit_flags(p) -> None:
    """Group-commit write-pipeline knobs, shared by the volume and
    combined-server commands (storage/commit.py + the native fronts)."""
    p.add_argument(
        "-commit.durability", dest="commit_durability",
        default="buffered", choices=["buffered", "batch", "sync"],
        help="write ack contract: buffered = ack after the userspace "
             "append (today's semantics), batch = ack only after the "
             "covering group-commit fsync (~1 fsync/batch), sync = "
             "per-write fsync oracle; recorded per response in the "
             "X-Sw-Durability header")
    p.add_argument(
        "-commit.maxDelay", dest="commit_max_delay", type=float,
        default=0.002,
        help="seconds the group-commit batch window stays open after "
             "its first write before the covering fsync (default "
             "0.002); smaller = lower ack latency, larger = more "
             "coalescing")
    p.add_argument(
        "-commit.maxBytes", dest="commit_max_bytes", type=int,
        default=4 << 20,
        help="bytes that close the group-commit batch window early, "
             "before -commit.maxDelay elapses (default 4MiB)")


def _add_ec_backend_flag(p) -> None:
    """-ec.backend, shared by the volume and combined-server commands;
    a name the registry does not know fails at parse time."""
    from .ec.backend import backend_names

    p.add_argument("-ec.backend", dest="ec_backend", default="auto",
                   choices=backend_names(),
                   help="erasure-coding codec: auto (measured-curve "
                        "router) | native | numpy | pallas | "
                        "mesh (all local devices)")


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """The command line, parsed: every subcommand and its flags."""
    parser = argparse.ArgumentParser(
        prog="seaweedfs-tpu",
        description="TPU-native distributed object store")
    parser.add_argument(
        "-cpuprofile", default="",
        help="write a cProfile dump here on exit (the reference's "
             "grace.SetupProfiling, util/grace/pprof.go:11); place "
             "BEFORE the subcommand")
    parser.add_argument(
        "-v", dest="verbosity", type=int, default=0,
        help="log verbosity for glog.v() messages (the reference's "
             "-v); place BEFORE the subcommand")
    parser.add_argument(
        "-vmodule", default="",
        help="per-file log levels, e.g. store=2,volume_server=3")
    parser.add_argument(
        "-memprofile", default="",
        help="write a tracemalloc top-allocations report here on exit "
             "(the reference's -memprofile); place BEFORE the "
             "subcommand")
    parser.add_argument(
        "-metrics.address", dest="metrics_address", default="",
        help="Prometheus pushgateway address to push metrics to "
             "(stats/metrics.go pusher); place BEFORE the subcommand")
    parser.add_argument(
        "-metrics.intervalSec", dest="metrics_interval", type=float,
        default=15.0)
    parser.add_argument(
        "-trace.slowThreshold", dest="trace_slow_threshold", type=float,
        default=1.0,
        help="emit one structured glog line with the full span tree "
             "for root requests slower than this many seconds "
             "(<= 0 disables); place BEFORE the subcommand")
    parser.add_argument(
        "-trace.bufferSize", dest="trace_buffer_size", type=int,
        default=1024,
        help="spans kept in the in-process ring served at "
             "/debug/traces; place BEFORE the subcommand")
    parser.add_argument(
        "-trace.sample", dest="trace_sample", type=float, default=1.0,
        help="head-sampling fraction (0..1) of traces shipped to the "
             "master's span collector; the verdict hashes the trace-id "
             "so every process keeps the same traces; place BEFORE "
             "the subcommand")
    parser.add_argument(
        "-trace.otlpUrl", dest="trace_otlp_url", default="",
        help="master only: push collected traces as OTLP/JSON to this "
             "HTTP endpoint (e.g. a Jaeger/Tempo collector's "
             "/v1/traces); place BEFORE the subcommand")
    parser.add_argument(
        "-fault.spec", dest="fault_spec", default="",
        help="deterministic fault injection for internal hops, e.g. "
             "'volume:read:error=0.05,filer:*:delay=30ms' "
             "(service:op:kind=value, comma-separated; also via "
             "SEAWEEDFS_TPU_FAULT_SPEC); place BEFORE the subcommand")
    parser.add_argument(
        "-fault.seed", dest="fault_seed", type=int, default=0,
        help="RNG seed for -fault.spec error draws (same seed + same "
             "request sequence = same chaos); place BEFORE the "
             "subcommand")
    parser.add_argument(
        "-retry.maxAttempts", dest="retry_max_attempts", type=int,
        default=None,
        help="attempts per internal hop (default 3); place BEFORE "
             "the subcommand")
    parser.add_argument(
        "-retry.baseDelay", dest="retry_base_delay", type=float,
        default=None,
        help="first-retry backoff cap in seconds (full jitter, "
             "default 0.02); place BEFORE the subcommand")
    parser.add_argument(
        "-retry.maxDelay", dest="retry_max_delay", type=float,
        default=None,
        help="backoff cap in seconds (default 1.0); place BEFORE the "
             "subcommand")
    parser.add_argument(
        "-retry.edgeBudget", dest="retry_edge_budget", type=float,
        default=None,
        help="overall deadline in seconds minted at the s3/filer edge "
             "when the client sent no X-Sw-Deadline (default 300); "
             "place BEFORE the subcommand")
    parser.add_argument(
        "-breaker.failures", dest="breaker_failures", type=int,
        default=None,
        help="consecutive connection failures that open a peer's "
             "circuit breaker (default 5); place BEFORE the subcommand")
    parser.add_argument(
        "-breaker.reset", dest="breaker_reset", type=float,
        default=None,
        help="seconds an open breaker waits before its half-open "
             "probe (default 5); place BEFORE the subcommand")
    parser.add_argument(
        "-hedge.delay", dest="hedge_delay", type=float, default=None,
        help="seconds a replica read waits before hedging to an "
             "alternate location (default 0.35); place BEFORE the "
             "subcommand")
    parser.add_argument(
        "-qos.enabled", dest="qos_enabled", action="store_true",
        help="per-tenant QoS + overload shedding at the s3/filer "
             "gateway edge (tenant = access key at s3, first path "
             "segment at the filer); place BEFORE the subcommand")
    parser.add_argument(
        "-qos.rate", dest="qos_rate", type=float, default=None,
        help="default per-tenant byte rate at the gateway edge "
             "(bytes/sec; 0 = unlimited); place BEFORE the subcommand")
    parser.add_argument(
        "-qos.burst", dest="qos_burst", type=float, default=None,
        help="default per-tenant burst allowance in bytes (default "
             "max(64KiB, rate/8)); place BEFORE the subcommand")
    parser.add_argument(
        "-qos.maxTenants", dest="qos_max_tenants", type=int,
        default=None,
        help="distinct tenant buckets a gateway tracks before later "
             "tenants share the __overflow__ bucket — bounds both "
             "memory and the tenant metric label (default 256); "
             "place BEFORE the subcommand")
    parser.add_argument(
        "-qos.maxDelay", dest="qos_max_delay", type=float,
        default=None,
        help="seconds of quoted queue delay beyond which a request "
             "is shed with 503 instead of paced (default 2.0); "
             "requests whose X-Sw-Deadline budget is smaller than "
             "the quote are shed regardless; place BEFORE the "
             "subcommand")
    parser.add_argument(
        "-qos.requestFloor", dest="qos_request_floor", type=int,
        default=None,
        help="minimum bytes charged per request so body-less ops "
             "(GET/HEAD/LIST) are shaped too (default 4096); place "
             "BEFORE the subcommand")
    parser.add_argument(
        "-qos.spec", dest="qos_spec", default="",
        help="path to a per-tenant JSON spec "
             "('{\"default\": {\"rate\":...}, \"tenants\": {\"akid\": "
             "{\"rate\":..., \"priority\":...}}}'), hot-reloaded on "
             "mtime change; place BEFORE the subcommand")
    parser.add_argument(
        "-telemetry.enabled", dest="telemetry_enabled",
        type=lambda s: s.lower() not in ("0", "false", "no"),
        default=True,
        help="record workload sketches (per-volume heat histograms, "
             "per-tenant demand) and ship them on the heartbeat; "
             "false disables every record path (default true); "
             "place BEFORE the subcommand")
    parser.add_argument(
        "-telemetry.alpha", dest="telemetry_alpha", type=float,
        default=None,
        help="relative-error bound of the quantile sketches: any "
             "reported quantile is within alpha of the true value "
             "(default 0.01 = 1%%); place BEFORE the subcommand")
    parser.add_argument(
        "-telemetry.window", dest="telemetry_window", type=float,
        default=None,
        help="sliding-window horizon in seconds for workload "
             "sketches; older samples age out (default 300); place "
             "BEFORE the subcommand")
    parser.add_argument(
        "-security", default="",
        help="path to a security config JSON (scaffold "
             "-config=security): enables HTTPS (+ optional mutual "
             "TLS) on this process's listeners; place BEFORE the "
             "subcommand. Clients trust the CA via REQUESTS_CA_BUNDLE/"
             "SSL_CERT_FILE")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("master", help="start a master server")
    p.add_argument("-port", type=int, default=9333)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-volumeSizeLimitMB", type=int, default=30 * 1024)
    p.add_argument("-defaultReplication", default="000")
    p.add_argument("-jwt.secret", dest="jwt_secret", default="")
    p.add_argument("-peers", default="",
                   help="comma-separated ip:port of all masters (HA mode)")
    p.add_argument("-raftDir", dest="raft_dir", default="",
                   help="raft log/term persistence dir")
    p.add_argument("-sequencer", default="memory",
                   choices=["memory", "snowflake"],
                   help="file-id sequencer (HA masters force "
                        "snowflake)")
    p.add_argument("-admin.scripts", dest="admin_scripts",
                   default="",
                   help="semicolon-separated shell maintenance commands "
                        "run periodically by the leader, e.g. "
                        "'volume.vacuum; volume.fix.replication'")
    p.add_argument("-admin.scriptInterval",
                   dest="admin_script_interval", type=float,
                   default=60.0)
    p.add_argument("-repair.enabled", dest="repair_enabled",
                   action="store_true",
                   help="drive automatic repair of under-replicated "
                        "volumes and under-parity EC volumes from the "
                        "redundancy watchdog queue (tracking and "
                        "/debug/repair reporting are always on)")
    p.add_argument("-repair.interval", dest="repair_interval",
                   type=float, default=10.0,
                   help="seconds between watchdog deficit scans; "
                        "heartbeat register/loss deltas also trigger "
                        "an immediate scan")
    p.add_argument("-repair.concurrency", dest="repair_concurrency",
                   type=int, default=2,
                   help="max repairs (volume re-replications / EC "
                        "shard rebuilds) running at once")
    p.add_argument("-repair.maxAttempts", dest="repair_max_attempts",
                   type=int, default=5,
                   help="attempts per repair task before giving up; "
                        "retries back off with the shared -retry.* "
                        "full-jitter policy")
    p.add_argument("-repair.grace", dest="repair_grace",
                   type=float, default=0.0,
                   help="seconds a deficit must persist before repair "
                        "starts (rides out transient restarts; 0 = "
                        "repair on first scan)")
    p.add_argument("-repair.maxBytesPerSec",
                   dest="repair_max_bytes_per_sec",
                   type=float, default=0.0,
                   help="per-node repair byte-rate cap: every repair "
                        "copy/reconstruction read debits a shared "
                        "token bucket on its source AND destination "
                        "volume server, so bulk repair cannot "
                        "saturate the data plane after a rack loss "
                        "(fill/debt live in /cluster/status; 0 = "
                        "unshaped)")
    p.add_argument("-repair.partialEc", dest="repair_partial_ec",
                   type=lambda s: s.lower() not in
                   ("0", "false", "no"),
                   default=True,
                   help="rebuild a lost EC shard from a partial-"
                        "stripe degraded read of only the k shard "
                        "ranges reconstruction needs, instead of "
                        "borrowing every surviving shard file "
                        "(repair_read_bytes_total{mode} accounts the "
                        "saving; false = always full-stripe)")
    p.add_argument("-tier.enabled", dest="tier_enabled",
                   action="store_true",
                   help="drive the tiered-storage lifecycle (hot -> "
                        "warm EC -> cold remote) from the master "
                        "tiering controller; heat tracking and "
                        "/debug/tiering reporting are always on")
    p.add_argument("-tier.interval", dest="tier_interval",
                   type=float, default=30.0,
                   help="seconds between tiering heat scans; "
                        "heartbeats also trigger an immediate scan")
    p.add_argument("-tier.concurrency", dest="tier_concurrency",
                   type=int, default=1,
                   help="max tier transitions (seal/offload/recall) "
                        "running at once")
    p.add_argument("-tier.sealAfterIdle", dest="tier_seal_after_idle",
                   type=float, default=3600.0,
                   help="seconds a plain volume must be idle (no "
                        "reads or writes) before it is sealed and "
                        "erasure-coded into the warm tier")
    p.add_argument("-tier.offloadAfterIdle",
                   dest="tier_offload_after_idle",
                   type=float, default=7200.0,
                   help="seconds an EC volume must go unread before "
                        "its shard bytes are offloaded to the remote "
                        "cold tier (indexes stay local)")
    p.add_argument("-tier.recallReads", dest="tier_recall_reads",
                   type=int, default=3,
                   help="reads within -tier.recallWindow that recall "
                        "a remote volume back to the hot tier")
    p.add_argument("-tier.recallWindow", dest="tier_recall_window",
                   type=float, default=300.0,
                   help="trailing window (seconds) over which "
                        "-tier.recallReads is counted")
    p.add_argument("-tier.maxAttempts", dest="tier_max_attempts",
                   type=int, default=5,
                   help="attempts per tier transition before giving "
                        "up; retries back off with the shared "
                        "-retry.* full-jitter policy")
    p.add_argument("-tier.maxBytesPerSec",
                   dest="tier_max_bytes_per_sec",
                   type=float, default=0.0,
                   help="per-node tier byte-rate cap: every offload "
                        "upload and recall download debits a shared "
                        "token bucket on its volume server, so bulk "
                        "tier movement cannot saturate the data "
                        "plane (fill/debt live in /cluster/status; "
                        "0 = unshaped)")
    p.add_argument("-tier.remote", dest="tier_remote", default="",
                   help="cold-tier destination: JSON client conf "
                        "('{\"type\": \"s3\", ...}') or the "
                        "local:<root> shorthand; offload stays off "
                        "until set")
    p.add_argument("-tier.stateDir", dest="tier_state_dir", default="",
                   help="dir persisting the per-volume tier state "
                        "machine so transitions resume across master "
                        "restarts (empty = in-memory only)")
    p.add_argument("-master.traceStore", dest="trace_store_size",
                   type=int, default=2048,
                   help="max traces kept in the cluster span "
                        "collector (tail-based retention pins "
                        "error/slow traces)")
    p.add_argument("-master.scrapeInterval", dest="scrape_interval",
                   type=float, default=10.0,
                   help="seconds between metrics-federation sweeps "
                        "over every registered node's /metrics")
    p.add_argument("-advisor.sealQuantile",
                   dest="advisor_seal_quantile", type=float,
                   default=0.95,
                   help="idle-gap quantile the auto-seal advisor "
                        "targets: it recommends -tier.sealAfterIdle "
                        "just above this fraction of observed "
                        "inter-access gaps (default 0.95)")
    p.add_argument("-advisor.demandQuantile",
                   dest="advisor_demand_quantile", type=float,
                   default=0.9,
                   help="per-tenant demand quantile the QoS advisor "
                        "sizes provisioned rates against "
                        "(default 0.9)")
    p.add_argument("-advisor.headroom", dest="advisor_headroom",
                   type=float, default=1.5,
                   help="multiplier applied on top of observed "
                        "demand/idle quantiles before recommending "
                        "a threshold (default 1.5)")

    p = sub.add_parser("master.follower",
                       help="read-only master follower for lookup traffic")
    p.add_argument("-port", type=int, default=9334)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-masters", default="http://127.0.0.1:9333",
                   help="comma-separated master urls to follow")

    p = sub.add_parser("volume", help="start a volume server")
    p.add_argument("-port", type=int, default=8080)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-dir", default="./data", help="comma-separated dirs")
    p.add_argument("-max", type=int, default=8)
    p.add_argument("-mserver", default="127.0.0.1:9333")
    p.add_argument("-dataCenter", default="DefaultDataCenter")
    p.add_argument("-rack", default="DefaultRack")
    _add_ec_backend_flag(p)
    p.add_argument("-ec.code", dest="ec_code", default="",
                   help="erasure-code family new EC volumes are "
                        "encoded with: 10.4 (RS default) | 28.4 "
                        "(wide RS) | lrc-k.l.g e.g. lrc-12.3.2 "
                        "(k data, l local XOR parities, g global "
                        "parities; single-shard repair reads one "
                        "local group instead of k shards); recorded "
                        "per volume so mixed-code clusters decode "
                        "correctly")
    p.add_argument("-ec.mesh.devices", dest="ec_mesh_devices",
                   type=int, default=0,
                   help="devices the mesh codec spans "
                        "(0 = all local devices)")
    p.add_argument("-ec.mesh.col", dest="ec_mesh_col", type=int,
                   default=0,
                   help="column-parallel axis of the mesh codec's "
                        "(vol, col) grid; must divide the device "
                        "count (0 = heuristic)")
    p.add_argument("-index", default="memory",
                   help="needle map kind: memory | compact | btree "
                        "(on-disk index for RAM-constrained servers)")
    p.add_argument("-disk", default="hdd",
                   help="disk class of this server (hdd | ssd)")
    p.add_argument("-concurrentUploadLimitMB", dest="upload_limit_mb",
                   type=int, default=256,
                   help="limit total in-flight upload bytes (0 = off)")
    p.add_argument("-concurrentDownloadLimitMB",
                   dest="download_limit_mb", type=int, default=256,
                   help="limit total in-flight download bytes (0 = off)")
    p.add_argument("-dataplane", default="auto",
                   choices=["auto", "native", "python"],
                   help="object hot-path server: native = C++ epoll "
                        "front (GET/POST by fid), python = asyncio "
                        "only, auto = native when the library builds")
    p.add_argument("-jwt.secret", dest="jwt_secret", default="",
                   help="HS256 secret for write authorization; must "
                        "match the master's -jwt.secret")
    _add_commit_flags(p)

    p = sub.add_parser("server", help="combined master+volume(+filer+s3)")
    p.add_argument("-dir", default="./data")
    p.add_argument("-master.port", dest="master_port", type=int,
                   default=9333)
    p.add_argument("-volume.port", dest="volume_port", type=int,
                   default=8080)
    p.add_argument("-filer", action="store_true")
    p.add_argument("-filer.port", dest="filer_port", type=int, default=8888)
    p.add_argument("-filer.native", dest="filer_native", default="auto",
                   choices=["auto", "native", "python"],
                   help="native C++ filer front for plain-file "
                        "GET/PUT/HEAD/DELETE (needs -dataplane native; "
                        "listings, renames and every other verb relay "
                        "to the python filer app)")
    p.add_argument("-filer.native.workers", dest="filer_native_workers",
                   type=int, default=2,
                   help="relay worker threads of the native filer "
                        "front (requests it cannot serve natively are "
                        "proxied to the python filer app)")
    p.add_argument("-s3", action="store_true")
    p.add_argument("-s3.port", dest="s3_port", type=int, default=8333)
    p.add_argument("-s3.config", dest="s3_config", default="",
                   help="json file with s3 identities")
    p.add_argument("-s3.native", dest="s3_native", default="auto",
                   choices=["auto", "native", "python"],
                   help="native C++ S3 front for small-object PUT/GET "
                        "(needs -dataplane native; everything else "
                        "relays to the python S3 app)")
    p.add_argument("-dataplane", default="auto",
                   choices=["auto", "native", "python"],
                   help="C++ front for the volume hot path")
    p.add_argument("-filer.store", dest="filer_store", default="sqlite")
    p.add_argument("-filer.store.shards", dest="filer_store_shards",
                   type=int, default=0,
                   help="partition the filer namespace across N "
                        "independent -filer.store engines (bucket/"
                        "first-segment routing, consistent-hash ring; "
                        "compaction stays per-shard); 0 = single store")
    p.add_argument("-filer.cache.entries", dest="filer_cache_entries",
                   type=int, default=0,
                   help="read-through metadata cache: max cached "
                        "entries (positive + negative), exactly "
                        "invalidated via the meta event log; "
                        "0 = cache off")
    p.add_argument("-filer.cache.pages", dest="filer_cache_pages",
                   type=int, default=0,
                   help="read-through metadata cache: max cached "
                        "directory-listing pages; 0 = default when "
                        "-filer.cache.entries is set, else off")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-volumeSizeLimitMB", type=int, default=1024)
    _add_ec_backend_flag(p)
    p.add_argument("-ec.code", dest="ec_code", default="",
                   help="erasure-code family new EC volumes are "
                        "encoded with: 10.4 (RS default) | 28.4 "
                        "(wide RS) | lrc-k.l.g e.g. lrc-12.3.2 "
                        "(k data, l local XOR parities, g global "
                        "parities; single-shard repair reads one "
                        "local group instead of k shards); recorded "
                        "per volume so mixed-code clusters decode "
                        "correctly")
    p.add_argument("-ec.mesh.devices", dest="ec_mesh_devices",
                   type=int, default=0,
                   help="devices the mesh codec spans "
                        "(0 = all local devices)")
    p.add_argument("-ec.mesh.col", dest="ec_mesh_col", type=int,
                   default=0,
                   help="column-parallel axis of the mesh codec's "
                        "(vol, col) grid; must divide the device "
                        "count (0 = heuristic)")
    p.add_argument("-index", default="memory",
                   help="needle map kind: memory | compact | btree "
                        "(on-disk index for RAM-constrained servers)")
    _add_commit_flags(p)

    p = sub.add_parser("filer", help="start a filer server")
    p.add_argument("-port", type=int, default=8888)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-master", default="http://127.0.0.1:9333")
    p.add_argument("-store", default="memory",
                   help="metadata store: memory | sqlite | leveldb | "
                        "redis | redis_cluster (seed list in "
                        "-store.host) | etcd | mongodb | cassandra | "
                        "mysql | mysql2 | postgres | postgres2 "
                        "(per-bucket tables, O(1) bucket drop) | "
                        "elastic | arangodb | hbase | tikv | ydb | "
                        "rocksdb (needs librocksdb)")
    p.add_argument("-store.path", dest="store_path", default=":memory:")
    p.add_argument("-store.host", dest="store_host", default="")
    p.add_argument("-store.port", dest="store_port", type=int, default=0)
    p.add_argument("-store.user", dest="store_user", default="",
                   help="db username (mysql/postgres/cassandra)")
    p.add_argument("-store.password", dest="store_password", default="")
    p.add_argument("-store.database", dest="store_database", default="")
    p.add_argument("-filer.store.shards", dest="filer_store_shards",
                   type=int, default=0,
                   help="partition the filer namespace across N "
                        "independent -store engines (bucket/"
                        "first-segment routing, consistent-hash ring; "
                        "compaction stays per-shard); 0 = single store")
    p.add_argument("-filer.cache.entries", dest="filer_cache_entries",
                   type=int, default=0,
                   help="read-through metadata cache: max cached "
                        "entries (positive + negative), exactly "
                        "invalidated via the meta event log; "
                        "0 = cache off")
    p.add_argument("-filer.cache.pages", dest="filer_cache_pages",
                   type=int, default=0,
                   help="read-through metadata cache: max cached "
                        "directory-listing pages; 0 = default when "
                        "-filer.cache.entries is set, else off")
    p.add_argument("-filer.native", dest="filer_native", default="python",
                   choices=["auto", "native", "python"],
                   help="native C++ filer front for plain-file "
                        "GET/PUT/HEAD/DELETE; only the combined "
                        "`server` command can honor 'native' (the "
                        "front appends to an in-process volume store), "
                        "a standalone filer always serves python")
    p.add_argument("-filer.native.workers", dest="filer_native_workers",
                   type=int, default=2,
                   help="relay worker threads of the native filer "
                        "front (combined `server` mode only)")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-encryptVolumeData", dest="encrypt_volume_data",
                   action="store_true",
                   help="encrypt chunk data on volume servers "
                        "(AES-256-GCM, per-chunk keys in filer metadata)")
    p.add_argument("-saveToFilerLimit", dest="save_to_filer_limit",
                   type=int, default=0,
                   help="files smaller than this many bytes are stored "
                        "inside the filer metadata entry (no volume "
                        "round trip); per-request ?saveInside=true "
                        "forces it")

    p = sub.add_parser("s3", help="start an S3 gateway")
    p.add_argument("-port", type=int, default=8333)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-config", default="",
                   help="json file with s3 identities")

    p = sub.add_parser("ftp", help="start an FTP gateway")
    p.add_argument("-port", type=int, default=8021)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-filer.path", dest="filer_path", default="/")
    p.add_argument("-user", default="",
                   help="user:password (empty = anonymous)")

    p = sub.add_parser("filer.replicate",
                       help="mirror filer changes into a sink")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-path", default="/", help="source path prefix")
    p.add_argument("-sink", required=True,
                   help="local:<dir> | filer:<url>[,<destPath>] | "
                        "s3:<endpoint>,<bucket>[,<prefix>] | "
                        "gcs:<bucket>[,<prefix>[,<endpoint>]] | "
                        "azure:<account>,<key>,<container>[,<prefix>] | "
                        "b2:<keyId>,<appKey>,<bucket>[,<prefix>]")

    p = sub.add_parser("filer.sync",
                       help="active-active sync between two filers")
    p.add_argument("-a", required=True, help="filer A url")
    p.add_argument("-b", required=True, help="filer B url")
    p.add_argument("-path", default="/")
    p.add_argument("-oneWay", dest="one_way", action="store_true")

    p = sub.add_parser("filer.remote.sync",
                       help="push local writes under a remote mount "
                            "back to the cloud storage")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-dir", required=True, help="mounted directory")

    p = sub.add_parser("filer.remote.gateway",
                       help="mirror bucket creation/deletion and bucket "
                            "contents to the primary remote storage")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-createBucketAt", dest="create_bucket_at", default="",
                   help="remote storage name for new buckets "
                        "(defaults to the only configured storage)")
    p.add_argument("-createBucketWithRandomSuffix", dest="bucket_suffix",
                   action="store_true")
    p.add_argument("-include", default="",
                   help="glob of bucket names to mirror, e.g. s3*")
    p.add_argument("-exclude", default="",
                   help="glob of bucket names to skip, e.g. local*")

    p = sub.add_parser("filer.meta.backup",
                       help="continuous metadata backup to sqlite")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-path", default="/")
    p.add_argument("-o", dest="output", default="filer_meta_backup.db")

    p = sub.add_parser("filer.backup",
                       help="continuous file backup into a local dir "
                            "(filer.replicate with a local sink)")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-path", default="/", help="source path prefix")
    p.add_argument("-dir", required=True, help="local target directory")

    p = sub.add_parser("filer.meta.tail",
                       help="print the filer metadata event stream")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-path", default="/", help="path prefix filter")
    p.add_argument("-pattern", default="",
                   help="only events whose path contains this substring")

    p = sub.add_parser("mq.broker", help="start a message-queue broker")
    p.add_argument("-port", type=int, default=17777)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-master", default="http://127.0.0.1:9333")

    p = sub.add_parser("webdav", help="start a WebDAV gateway")
    p.add_argument("-port", type=int, default=7333)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-filer.path", dest="filer_path", default="/")

    p = sub.add_parser("iam", help="start an IAM API server")
    p.add_argument("-port", type=int, default=8111)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="http://127.0.0.1:8888")

    p = sub.add_parser("mount", help="FUSE-mount a filer directory")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-filer.path", dest="filer_path", default="/")
    p.add_argument("-dir", required=True, help="local mountpoint")
    p.add_argument("-cacheDir", dest="cache_dir", default="")
    p.add_argument("-writeMemoryLimitMB", dest="write_memory_limit_mb",
                   type=int, default=64,
                   help="dirty-write RAM cap per open file; writes past "
                        "it spill to a swap file (0 = 64MB default)")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-o", dest="mount_options", default="",
                   help="extra comma-separated fuse options "
                        "(allow_other, ro, ...)")
    p.add_argument("-disableXAttr", dest="disable_xattr",
                   action="store_true",
                   help="disable extended attribute support "
                        "(get/set/list/remove return ENOTSUP)")

    p = sub.add_parser(
        "fuse",
        help="/sbin/mount.fuse-style mount helper: "
             "`fuse <mountpoint> -o filer=...,filer.path=/,ro` "
             "(the reference's weed fuse, command/fuse.go) — lets "
             "/etc/fstab mount a filer via `mount -t fuse.seaweedfs`")
    p.add_argument("mountpoint")
    p.add_argument("-o", dest="fuse_options", default="",
                   help="comma-separated key=value options; recognised: "
                        "filer, filer.path, collection, replication, "
                        "cacheDir; everything else passes to fuse")

    p = sub.add_parser("shell", help="interactive admin shell")
    p.add_argument("-master", default="http://127.0.0.1:9333")
    p.add_argument("-filer", default="",
                   help="filer address for the cluster-wide admin lock")

    p = sub.add_parser("upload", help="upload files")
    p.add_argument("-master", default="http://127.0.0.1:9333")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-maxMB", dest="max_mb", type=int, default=0,
                   help="split files larger than this into chunk "
                        "needles + a manifest (submit.go maxMB)")
    p.add_argument("files", nargs="+")

    p = sub.add_parser("download", help="download a fid")
    p.add_argument("-master", default="http://127.0.0.1:9333")
    p.add_argument("-o", dest="output", default="")
    p.add_argument("fid")

    p = sub.add_parser("fix", help="offline: rebuild a volume's .idx "
                                   "by scanning its .dat")
    p.add_argument("-dir", default=".")
    p.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    p.add_argument("-collection", default="")

    for name, hlp in (("see.dat", "offline: dump every .dat record as "
                                  "JSON lines (debug inspector)"),
                      ("see.idx", "offline: dump every .idx entry as "
                                  "JSON lines (debug inspector)")):
        p = sub.add_parser(name, help=hlp)
        p.add_argument("-dir", default=".")
        p.add_argument("-volumeId", dest="volume_id", type=int,
                       required=True)
        p.add_argument("-collection", default="")

    p = sub.add_parser("compact", help="offline: vacuum a volume's "
                                       "deleted records")
    p.add_argument("-dir", default=".")
    p.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    p.add_argument("-collection", default="")

    p = sub.add_parser("export", help="offline: dump live needles to tar")
    p.add_argument("-dir", default=".")
    p.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    p.add_argument("-collection", default="")
    p.add_argument("-o", dest="output", default="")
    p.add_argument("-newerThanNs", dest="newer_than_ns", type=int,
                   default=0)

    p = sub.add_parser("filer.cat", help="print a filer file to stdout")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("path")

    p = sub.add_parser("filer.copy", help="upload local files/dirs to a "
                                          "filer directory")
    p.add_argument("-filer", default="http://127.0.0.1:8888")
    p.add_argument("-collection", default="")
    p.add_argument("-maxMB", dest="max_mb", type=int, default=0)
    p.add_argument("sources", nargs="+")
    p.add_argument("dest")

    p = sub.add_parser("backup", help="incrementally back up a volume "
                                      "to a local directory")
    p.add_argument("-server", "-master", dest="master",
                   default="http://127.0.0.1:9333")
    p.add_argument("-volumeId", dest="volume_id", type=int, required=True)
    p.add_argument("-dir", default=".")
    p.add_argument("-collection", default="")

    p = sub.add_parser("benchmark", help="write/read load generator")
    p.add_argument("-client", default="python",
                   choices=["python", "native"],
                   help="load generator: python threads (requests) or "
                        "the C++ keep-alive client — use native to "
                        "measure a native-dataplane server without the "
                        "client's GIL being the bottleneck")
    p.add_argument("-master", default="http://127.0.0.1:9333")
    p.add_argument("-n", type=int, default=1000)
    p.add_argument("-size", type=int, default=1024)
    p.add_argument("-c", dest="concurrency", type=int, default=16)
    p.add_argument("-collection", default="benchmark")
    p.add_argument("-replication", default="",
                   help="replica placement for the benchmark volumes "
                        "(e.g. 001); empty = master default")
    p.add_argument("-target", default="fid",
                   choices=["fid", "s3", "filer"],
                   help="fid = raw volume path (default); s3 = the "
                        "gateway path (SigV4 auth -> filer autochunk "
                        "-> assign -> volume); filer = the filer HTTP "
                        "path without S3 auth")
    p.add_argument("-s3.url", dest="s3_url",
                   default="http://127.0.0.1:8333")
    p.add_argument("-s3.access", dest="s3_access", default="")
    p.add_argument("-s3.secret", dest="s3_secret", default="")
    p.add_argument("-filer.url", dest="filer_url",
                   default="http://127.0.0.1:8888")
    p.add_argument("-bucket", default="benchbucket")

    p = sub.add_parser("scaffold", help="print a starter config "
                                        "template")
    p.add_argument("-config", default="filer",
                   help="filer | master | security | replication | "
                        "notification | s3 | shell")
    p.add_argument("-output", default="",
                   help="write to a file instead of stdout")

    p = sub.add_parser(
        "autocomplete",
        help="print shell tab-completion setup (the reference's "
             "autocomplete command); eval it or add to your rc file")
    p.add_argument("-shell", default="bash", choices=["bash", "zsh"])

    sub.add_parser("unautocomplete",
                   help="print how to remove shell completion")

    sub.add_parser("update",
                   help="self-update placeholder (no binary releases "
                        "in this distribution)")

    p = sub.add_parser("version")

    args = parser.parse_args(argv)
    args._subcommands = list(sub.choices)
    return args


# the subcommands whose process may build a device codec, and so
# compile: the volume server and the all-in-one server
_CODEC_COMMANDS = ("volume", "server")


def configure(args: argparse.Namespace) -> None:
    """Process-wide setup from the parsed flags (logging, tracing,
    retry, QoS, faults, telemetry; the compile cache for the commands
    that run a codec) — everything `main` does before dispatching."""
    if args.cmd in _CODEC_COMMANDS:
        from .ops import device

        device.setup_compile_cache()
    if args.verbosity or args.vmodule:
        from .utils import glog

        glog.set_verbosity(args.verbosity)
        glog.set_vmodule(args.vmodule)
    if args.metrics_address:
        from .utils import metrics as _metrics

        _metrics.start_push(args.metrics_address, job=args.cmd,
                            interval_seconds=args.metrics_interval)
    from .utils import tracing as _tracing

    _tracing.configure(slow_threshold=args.trace_slow_threshold,
                       buffer_size=args.trace_buffer_size,
                       sample_rate=args.trace_sample)
    # mesh shape knobs travel by env so the codec registry (and any
    # worker process it spawns) sees them without plumbing args through
    # every Store constructor
    if getattr(args, "ec_mesh_devices", 0):
        os.environ["SEAWEEDFS_TPU_EC_MESH_DEVICES"] = str(
            args.ec_mesh_devices)
    if getattr(args, "ec_mesh_col", 0):
        os.environ["SEAWEEDFS_TPU_EC_MESH_COL"] = str(args.ec_mesh_col)
    # the default code family also travels by env: shell `ec.encode`
    # (in another process) and the probe fingerprint both consult it
    if getattr(args, "ec_code", ""):
        from .ec import geometry as _geo

        _geo.parse_code(args.ec_code)  # fail fast on a bad spec
        os.environ["SEAWEEDFS_TPU_EC_CODE"] = args.ec_code
    from .utils import faults as _faults
    from .utils import qos as _qos
    from .utils import retry as _retry
    from .utils import sketch as _sketch

    _faults.configure(spec=args.fault_spec or None,
                      seed=args.fault_seed or None)
    _retry.configure(max_attempts=args.retry_max_attempts,
                     base_delay=args.retry_base_delay,
                     max_delay=args.retry_max_delay,
                     edge_budget=args.retry_edge_budget,
                     breaker_failures=args.breaker_failures,
                     breaker_reset=args.breaker_reset,
                     hedge_delay=args.hedge_delay)
    _qos.configure(enabled=args.qos_enabled or None,
                   rate=args.qos_rate,
                   burst=args.qos_burst,
                   max_tenants=args.qos_max_tenants,
                   max_delay=args.qos_max_delay,
                   request_floor=args.qos_request_floor,
                   spec=args.qos_spec or None)
    _sketch.configure(enabled=args.telemetry_enabled,
                      alpha=args.telemetry_alpha,
                      window=args.telemetry_window)


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    configure(args)
    if args.memprofile:
        import tracemalloc

        tracemalloc.start(16)
    try:
        if args.cpuprofile:
            import cProfile

            prof = cProfile.Profile()
            prof.enable()
            try:
                return _dispatch(args)
            finally:
                prof.disable()
                prof.dump_stats(args.cpuprofile)
                print(f"cpu profile written to {args.cpuprofile}")
        return _dispatch(args)
    finally:
        if args.memprofile:
            import tracemalloc

            snap = tracemalloc.take_snapshot()
            with open(args.memprofile, "w") as f:
                for stat in snap.statistics("lineno")[:200]:
                    f.write(f"{stat}\n")
            print(f"memory profile written to {args.memprofile}")


def _dispatch(args) -> int:
    if args.cmd == "version":
        from . import __version__

        print(f"seaweedfs-tpu {__version__}")
        return 0
    if args.cmd == "autocomplete":
        cmds = " ".join(sorted(getattr(args, "_subcommands", [])))
        if args.shell == "bash":
            print(f"complete -W '{cmds}' seaweedfs-tpu\n"
                  f"complete -W '{cmds}' weed\n"
                  "# add the lines above to ~/.bashrc, or: "
                  "eval \"$(seaweedfs-tpu autocomplete)\"")
        else:
            print(f"compdef '_arguments \"1:command:({cmds})\"' "
                  "seaweedfs-tpu\n# add to ~/.zshrc after compinit")
        return 0
    if args.cmd == "unautocomplete":
        print("remove the 'complete -W ... seaweedfs-tpu' lines from "
              "your shell rc file (this build never edits it for you)")
        return 0
    if args.cmd == "update":
        print("seaweedfs-tpu is distributed as a Python package, not "
              "a downloadable binary; update it with your package "
              "manager / git checkout")
        return 1
    if args.cmd == "scaffold":
        from .scaffold import scaffold
        text = scaffold(args.config)
        if args.output:
            with open(args.output, "w") as f:
                f.write(text)
            print(f"wrote {args.output}")
        else:
            print(text, end="")
        return 0
    if args.cmd in ("see.dat", "see.idx"):
        import json as _json

        from .operation import tools
        it = (tools.see_dat if args.cmd == "see.dat" else
              tools.see_idx)(args.dir, args.volume_id, args.collection)
        for rec in it:
            print(_json.dumps(rec))
        return 0
    if args.cmd in ("fix", "compact", "export"):
        import json as _json

        from .operation import tools
        if args.cmd == "fix":
            out = tools.fix_volume(args.dir, args.volume_id,
                                   args.collection)
        elif args.cmd == "compact":
            out = tools.compact_volume(args.dir, args.volume_id,
                                       args.collection)
        else:
            dest = args.output or f"vol{args.volume_id}.tar"
            out = tools.export_volume(args.dir, args.volume_id, dest,
                                      args.collection,
                                      args.newer_than_ns)
        print(_json.dumps(out))
        return 0
    if args.cmd == "filer.cat":
        import sys as _sys

        with session().get(f"{args.filer.rstrip('/')}/"
                           f"{args.path.lstrip('/')}", stream=True,
                           timeout=600) as r:
            if r.status_code >= 300:
                print(r.text, file=_sys.stderr)
                return 1
            for chunk in r.iter_content(1 << 20):
                _sys.stdout.buffer.write(chunk)
        return 0
    if args.cmd == "filer.copy":
        return _run_filer_copy(args)
    if args.cmd == "backup":
        import json as _json

        from .operation.backup import backup_volume
        out = backup_volume(args.master, args.volume_id, args.dir,
                            collection=args.collection)
        print(_json.dumps(out))
        return 0
    if args.cmd == "master":
        return _run_master(args)
    if args.cmd == "master.follower":
        from .rpc.http import ServerThread, run_apps_forever
        from .server.master_follower import MasterFollower

        masters = [m.strip() if m.strip().startswith("http")
                   else f"http://{m.strip()}"
                   for m in args.masters.split(",") if m.strip()]
        mf = MasterFollower(masters)
        t = ServerThread(mf.build_app(), host=args.ip, port=args.port,
                         ssl_context=_ssl_ctx(args)).start()
        print(f"master follower listening on {t.url}, "
              f"following {masters}")
        run_apps_forever([t])
        return 0
    if args.cmd == "volume":
        return _run_volume(args)
    if args.cmd == "server":
        return _run_server(args)
    if args.cmd == "filer":
        return _run_filer(args)
    if args.cmd == "s3":
        return _run_s3(args)
    if args.cmd == "filer.replicate":
        return _run_replicate(args)
    if args.cmd == "filer.sync":
        import time as _t

        from .replication.filer_sync import FilerSync

        sync = FilerSync(args.a, args.b, path_prefix=args.path,
                         both_ways=not args.one_way)
        sync.start()
        print(f"syncing {args.a} <-> {args.b} under {args.path}")
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            sync.stop()
        return 0
    if args.cmd == "filer.remote.gateway":
        import time as _t

        from .remote_storage.gateway import RemoteGateway

        g = RemoteGateway(args.filer,
                          create_bucket_at=args.create_bucket_at,
                          bucket_suffix=args.bucket_suffix,
                          include=args.include, exclude=args.exclude)
        g.start()
        print(f"mirroring {args.filer}/buckets to remote storage "
              f"{g.create_bucket_at or '(none configured)'}")
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            g.stop()
        return 0
    if args.cmd == "filer.remote.sync":
        import time as _t

        from .remote_storage.sync import RemoteSyncWorker

        w = RemoteSyncWorker(args.filer, args.dir)
        w.start()
        print(f"pushing {args.filer}{args.dir} writes to "
              f"storage {w.mount.storage!r}")
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            w.stop()
        return 0
    if args.cmd == "filer.backup":
        import hashlib as _hashlib
        import os as _os
        import time as _t

        from .replication.replicator import Replicator
        from .replication.sink import LocalSink

        # per-target resume offset: two backups (different -dir or
        # -path) must not share/clobber one offset key
        target_id = _hashlib.sha256(
            f"{args.path}\x00{_os.path.abspath(args.dir)}".encode()
        ).hexdigest()[:16]
        r = Replicator(args.filer, LocalSink(args.dir),
                       path_prefix=args.path,
                       offset_key=f"replication/backup/{target_id}/"
                                  "offset")
        r.start()
        print(f"backing up {args.filer}{args.path} -> {args.dir}")
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            r.stop()
        return 0
    if args.cmd == "filer.meta.tail":
        import json as _json
        import time as _t

        from .rpc.meta_subscriber import MetaSubscriber

        def emit(ev: dict) -> None:
            entry = ev.get("new_entry") or ev.get("old_entry") or {}
            path = entry.get("full_path") or ev.get("directory", "")
            if args.pattern and args.pattern not in path:
                return
            print(_json.dumps(ev, separators=(",", ":")), flush=True)

        sub_ = MetaSubscriber(args.filer, args.path, emit)
        sub_.start()
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            sub_.stop()
        return 0
    if args.cmd == "filer.meta.backup":
        import time as _t

        from .replication.meta_backup import FilerMetaBackup

        b = FilerMetaBackup(args.filer, args.output,
                            path_prefix=args.path)
        b.start()
        print(f"backing up {args.filer}{args.path} metadata "
              f"to {args.output}")
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            b.stop()
        return 0
    if args.cmd == "ftp":
        import time as _t

        from .ftpd import FtpServer

        users = {}
        if args.user:
            u, _, pw = args.user.partition(":")
            users[u] = pw
        f = FtpServer(args.filer, port=args.port, host=args.ip,
                      root=args.filer_path, users=users,
                      anonymous=not users).start()
        print(f"ftp gateway listening on {args.ip}:{f.port}")
        try:
            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            f.stop()
        return 0
    if args.cmd == "mq.broker":
        from .mq.broker import BrokerServer
        from .rpc.http import ServerThread, run_apps_forever

        b = BrokerServer(args.filer, args.master)
        t = ServerThread(b.app, host=args.ip, port=args.port).start()
        b.address = t.address
        print(f"mq broker listening on {t.url}")
        run_apps_forever([t])
        return 0
    if args.cmd == "webdav":
        from .rpc.http import ServerThread, run_apps_forever
        from .webdav.server import WebDavServer

        w = WebDavServer(args.filer, root=args.filer_path)
        t = ServerThread(w.app, host=args.ip, port=args.port,
                         ssl_context=_ssl_ctx(args)).start()
        print(f"webdav listening on {t.url}")
        from .rpc.trace_push import master_from_filer

        _filer = args.filer if args.filer.startswith("http") else \
            f"http://{args.filer}"
        _start_span_pusher(lambda: master_from_filer(_filer), "webdav",
                           t.address)
        run_apps_forever([t])
        return 0
    if args.cmd == "iam":
        from .iam.server import IamApiServer
        from .rpc.http import ServerThread, run_apps_forever

        i = IamApiServer(args.filer)
        t = ServerThread(i.app, host=args.ip, port=args.port,
                         ssl_context=_ssl_ctx(args)).start()
        print(f"iam api listening on {t.url}")
        run_apps_forever([t])
        return 0
    if args.cmd == "mount":
        from .mount.fuse_adapter import mount

        mount(args.filer, args.dir, root=args.filer_path,
              options=args.mount_options or None,
              cache_dir=args.cache_dir or None,
              collection=args.collection, replication=args.replication,
              write_memory_limit=(args.write_memory_limit_mb
                                  or 64) << 20,
              disable_xattr=args.disable_xattr)
        return 0
    if args.cmd == "fuse":
        from .mount.fuse_adapter import mount

        known = {"filer": "http://127.0.0.1:8888", "filer.path": "/",
                 "collection": "", "replication": "", "cacheDir": "",
                 "disableXAttr": ""}
        passthrough = []
        for opt in (args.fuse_options or "").split(","):
            if not opt:
                continue
            k, sep, v = opt.partition("=")
            if k in known:
                known[k] = v if sep else "true"
            else:
                passthrough.append(opt)
        mount(known["filer"], args.mountpoint, root=known["filer.path"],
              options=",".join(passthrough) or None,
              cache_dir=known["cacheDir"] or None,
              collection=known["collection"],
              replication=known["replication"],
              disable_xattr=known["disableXAttr"] == "true")
        return 0
    if args.cmd == "shell":
        from .shell.repl import run_shell

        return run_shell(args.master, filer_url=args.filer)
    if args.cmd == "upload":
        from .operation import verbs

        for path in args.files:
            size = os.path.getsize(path)
            limit = args.max_mb << 20
            if limit and size > limit:
                # chunked submit (submit.go:134): one needle per
                # -maxMB span + a ?cm=true manifest needle
                import mimetypes

                from .operation.chunked_file import upload_chunked

                name = os.path.basename(path)

                def pieces(p=path, lim=limit):
                    with open(p, "rb") as f:
                        while True:
                            piece = f.read(lim)
                            if not piece:
                                return
                            yield piece

                fid, stored = upload_chunked(
                    args.master, pieces(), size, name,
                    mimetypes.guess_type(name)[0] or "",
                    limit, collection=args.collection,
                    replication=args.replication)
                print(json.dumps({"file": path, "fid": fid,
                                  "size": stored, "chunked": True}))
                continue
            with open(path, "rb") as f:
                data = f.read()
            fid = verbs.upload_data(
                args.master, data, name=os.path.basename(path),
                collection=args.collection, replication=args.replication)
            print(json.dumps({"file": path, "fid": fid,
                              "size": len(data)}))
        return 0
    if args.cmd == "download":
        from .operation import verbs
        from .wdclient.client import MasterClient

        mc = MasterClient(args.master)
        data = verbs.download(mc.lookup_file_id(args.fid))
        out = args.output or args.fid.replace(",", "_")
        with open(out, "wb") as f:
            f.write(data)
        print(f"{args.fid} -> {out} ({len(data)} bytes)")
        return 0
    if args.cmd == "benchmark":
        return _run_benchmark(args)
    return 1


def _start_span_pusher(master_url, service: str, instance: str):
    """Ship this process's finished spans to the master's collector
    (rpc/trace_push.py). `master_url` may be a callable for gateways
    that must resolve the master through their filer. Never fatal: a
    process that can't push still serves (drops are counted)."""
    from .rpc.trace_push import SpanPusher

    sp = SpanPusher(master_url, service, instance)
    sp.start()
    return sp


def _run_master(args) -> int:
    from .remote_storage.client import parse_remote_spec
    from .rpc.http import ServerThread, run_apps_forever
    from .server.master_server import MasterServer

    peers = [p.strip() for p in args.peers.split(",") if p.strip()]
    raft_dir = args.raft_dir
    if peers and not raft_dir:
        # raft safety requires durable term/vote/log: a master that
        # restarts without them could vote twice in one term and elect
        # two leaders
        raft_dir = os.path.join(
            os.path.expanduser("~"), ".seaweedfs_tpu", "raft")
        print(f"-raftDir not set; persisting raft state to {raft_dir}")
    if raft_dir:
        os.makedirs(raft_dir, exist_ok=True)
    scripts = [s.strip() for s in args.admin_scripts.split(";")
               if s.strip()]
    ms = MasterServer(volume_size_limit=args.volumeSizeLimitMB << 20,
                      default_replication=args.defaultReplication,
                      jwt_secret=args.jwt_secret,
                      sequencer=args.sequencer,
                      me=f"{args.ip}:{args.port}", peers=peers,
                      raft_state_dir=raft_dir or None,
                      admin_scripts=scripts,
                      admin_script_interval=args.admin_script_interval,
                      repair_enabled=args.repair_enabled,
                      repair_interval=args.repair_interval,
                      repair_concurrency=args.repair_concurrency,
                      repair_max_attempts=args.repair_max_attempts,
                      repair_grace=args.repair_grace,
                      repair_max_bytes_per_sec=(
                          args.repair_max_bytes_per_sec),
                      repair_partial_ec=args.repair_partial_ec,
                      tier_enabled=args.tier_enabled,
                      tier_interval=args.tier_interval,
                      tier_concurrency=args.tier_concurrency,
                      tier_seal_after_idle=args.tier_seal_after_idle,
                      tier_offload_after_idle=(
                          args.tier_offload_after_idle),
                      tier_recall_reads=args.tier_recall_reads,
                      tier_recall_window=args.tier_recall_window,
                      tier_max_attempts=args.tier_max_attempts,
                      tier_max_bytes_per_sec=(
                          args.tier_max_bytes_per_sec),
                      tier_remote=(
                          parse_remote_spec(args.tier_remote)
                          if args.tier_remote else None),
                      tier_state_dir=args.tier_state_dir,
                      trace_store_size=args.trace_store_size,
                      scrape_interval=args.scrape_interval,
                      otlp_url=args.trace_otlp_url,
                      advisor_seal_quantile=args.advisor_seal_quantile,
                      advisor_demand_quantile=(
                          args.advisor_demand_quantile),
                      advisor_headroom=args.advisor_headroom)
    t = ServerThread(ms.app, host=args.ip, port=args.port,
                     ssl_context=_ssl_ctx(args)).start()
    ms.admin_scripts_url = t.url
    print(f"master listening on {t.url}")
    run_apps_forever([t])
    return 0


def _run_volume(args) -> int:
    from .rpc.http import ServerThread, run_apps_forever
    from .server.volume_server import VolumeServer
    from .storage.store import Store

    dirs = args.dir.split(",")
    store = Store(dirs, ip=args.ip, port=args.port,
                  ec_backend=args.ec_backend,
                  needle_map_kind=args.index)
    for loc in store.locations:
        loc.max_volumes = args.max
    # scheme normalization for each master happens inside VolumeServer
    vs = VolumeServer(store, args.mserver, data_center=args.dataCenter,
                      rack=args.rack, disk_type=args.disk,
                      jwt_secret=args.jwt_secret,
                      concurrent_upload_limit=args.upload_limit_mb << 20,
                      concurrent_download_limit=args.download_limit_mb
                      << 20,
                      commit_durability=args.commit_durability,
                      commit_max_delay=args.commit_max_delay,
                      commit_max_bytes=args.commit_max_bytes)
    native_port = _start_volume_front(vs, args, dirs)
    if native_port is None:
        t = ServerThread(vs.app, host=args.ip, port=args.port).start()
        store.port = t.port
        store.public_url = t.address
        print(f"volume server listening on {t.url}, dirs={dirs}")
    else:
        t = vs._backend_thread
        store.port = native_port
        store.public_url = f"{args.ip}:{native_port}"
        print(f"volume server listening on http://{store.public_url} "
              f"(native data plane; python backend :{t.port}), "
              f"dirs={dirs}")
    master = args.mserver.split(",")[0].strip()
    if not master.startswith("http"):
        master = "http://" + master
    _start_span_pusher(master, "volume", store.public_url)
    run_apps_forever([t])
    return 0


def _start_volume_front(vs, args, dirs) -> int | None:
    """Try to put the C++ data plane in front (volume server only).
    Returns the public port, or None to serve pure-Python."""
    mode = getattr(args, "dataplane", "auto")
    if mode == "python":
        return None
    from .native import dataplane as dpmod
    from .rpc.http import ServerThread

    if not dpmod.available():
        if mode == "native":
            raise SystemExit("-dataplane=native: g++ / prebuilt "
                             "libseaweed_dataplane.so not found")
        return None
    # build/load the library BEFORE the backend thread starts: once the
    # backend runs, stopping it would fire _on_cleanup -> store.close(),
    # leaving nothing servable — so all graceful fallback happens here
    try:
        dpmod._load()
    except Exception as e:
        if mode == "native":
            raise
        print(f"native data plane unavailable ({e}); "
              "serving pure-Python", file=sys.stderr)
        return None
    # past this point failures are fatal, exactly like the pure-Python
    # server failing to bind its port
    backend = ServerThread(vs.app, host="127.0.0.1", port=0).start()
    vs._backend_thread = backend
    return vs.enable_native(args.port, backend.port, listen_ip=args.ip)


def _run_replicate(args) -> int:
    import time as _t

    from .replication import Replicator, make_sink

    kind, _, rest = args.sink.partition(":")
    parts = rest.split(",")
    if kind == "local":
        sink = make_sink("local", directory=parts[0])
    elif kind == "filer":
        sink = make_sink("filer", filer_url=parts[0],
                         dest_path=parts[1] if len(parts) > 1 else "/")
    elif kind == "s3":
        sink = make_sink("s3", endpoint=parts[0], bucket=parts[1],
                         prefix=parts[2] if len(parts) > 2 else "")
    elif kind == "gcs":
        sink = make_sink(
            "gcs", bucket=parts[0],
            prefix=parts[1] if len(parts) > 1 else "",
            endpoint=parts[2] if len(parts) > 2 else "")
    elif kind == "azure":
        sink = make_sink(
            "azure", account=parts[0], key=parts[1],
            container=parts[2],
            prefix=parts[3] if len(parts) > 3 else "")
    elif kind == "b2":
        sink = make_sink(
            "b2", key_id=parts[0], application_key=parts[1],
            bucket=parts[2],
            prefix=parts[3] if len(parts) > 3 else "")
    else:
        print(f"unknown sink kind {kind!r}")
        return 1
    r = Replicator(args.filer, sink, path_prefix=args.path)
    r.start()
    print(f"replicating {args.filer}{args.path} -> {args.sink}")
    try:
        while True:
            _t.sleep(3600)
    except KeyboardInterrupt:
        r.stop()
    return 0


def _run_filer(args) -> int:
    from .rpc.http import ServerThread, run_apps_forever
    from .server.filer_server import FilerServer

    if getattr(args, "filer_native", "python") == "native":
        raise SystemExit(
            "-filer.native=native needs an in-process volume store: "
            "use the combined `server` command with -dataplane native")
    master = args.master if args.master.startswith("http") else \
        f"http://{args.master}"
    store_options = {}
    if args.store_host:
        store_options["host"] = args.store_host
    if args.store_port:
        store_options["port"] = args.store_port
    if args.store_user:
        store_options["user"] = args.store_user
    if args.store_password:
        store_options["password"] = args.store_password
    if args.store_database:
        store_options["database"] = args.store_database
    fs = FilerServer(master, store=args.store, store_path=args.store_path,
                     collection=args.collection,
                     replication=args.replication,
                     store_options=store_options,
                     cipher=args.encrypt_volume_data,
                     save_to_filer_limit=args.save_to_filer_limit,
                     store_shards=args.filer_store_shards,
                     cache_entries=args.filer_cache_entries,
                     cache_pages=args.filer_cache_pages)
    t = ServerThread(fs.app, host=args.ip, port=args.port,
                     ssl_context=_ssl_ctx(args)).start()
    fs.address = t.address
    print(f"filer listening on {t.url} (store={args.store})")
    _start_span_pusher(master, "filer", t.address)
    run_apps_forever([t])
    return 0


def _run_s3(args) -> int:
    from .rpc.http import ServerThread, run_apps_forever
    from .s3.server import S3ApiServer

    filer = args.filer if args.filer.startswith("http") else \
        f"http://{args.filer}"
    config = None
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
    s3 = S3ApiServer(filer, iam_config=config)
    t = ServerThread(s3.app, host=args.ip, port=args.port,
                     ssl_context=_ssl_ctx(args)).start()
    print(f"s3 gateway listening on {t.url}")
    from .rpc.trace_push import master_from_filer

    # gateways only know their filer; re-resolving per flush keeps the
    # pusher pointed at the master across failovers
    _start_span_pusher(lambda: master_from_filer(filer), "s3", t.address)
    run_apps_forever([t])
    return 0


def _run_server(args) -> int:
    from .rpc.http import run_apps_forever

    run_apps_forever(start_server(args))
    return 0


def start_server(args: argparse.Namespace) -> list:
    """Start the all-in-one node (`server`): master + volume server,
    plus filer/S3 when asked, each on its own ServerThread. Returns the
    threads; the caller serves them and stops them."""
    from .rpc.http import ServerThread
    from .server.master_server import MasterServer
    from .server.volume_server import VolumeServer
    from .storage.store import Store

    threads = []
    ms = MasterServer(volume_size_limit=args.volumeSizeLimitMB << 20)
    mt = ServerThread(ms.app, host=args.ip, port=args.master_port).start()
    ms.admin_scripts_url = mt.url
    threads.append(mt)
    print(f"master listening on {mt.url}")

    vol_dir = os.path.join(args.dir, "volume")
    os.makedirs(vol_dir, exist_ok=True)
    store = Store([vol_dir], ip=args.ip, port=args.volume_port,
                  ec_backend=args.ec_backend,
                  needle_map_kind=args.index)
    vs = VolumeServer(store, mt.url,
                      commit_durability=args.commit_durability,
                      commit_max_delay=args.commit_max_delay,
                      commit_max_bytes=args.commit_max_bytes)

    class _VolArgs:  # reuse the standalone volume front resolution
        dataplane = args.dataplane
        port = args.volume_port
        ip = args.ip

    public = _start_volume_front(vs, _VolArgs, [vol_dir])
    native_volume = public is not None
    if native_volume:
        vt = vs._backend_thread
        store.port = public
        store.public_url = f"{args.ip}:{public}"
        print(f"volume server listening on http://{args.ip}:{public} "
              f"(native data plane; python backend :{vt.port})")
    else:
        vt = ServerThread(vs.app, host=args.ip,
                          port=args.volume_port).start()
        store.port = vt.port
        store.public_url = vt.address
        print(f"volume server listening on {vt.url}")
    threads.append(vt)

    if args.filer or args.s3:
        from .server.filer_server import FilerServer

        filer_dir = os.path.join(args.dir, "filer")
        os.makedirs(filer_dir, exist_ok=True)
        fs = FilerServer(mt.url, store=args.filer_store,
                         store_path=os.path.join(filer_dir, "filer.db"),
                         store_shards=args.filer_store_shards,
                         cache_entries=args.filer_cache_entries,
                         cache_pages=args.filer_cache_pages)
        want_native_filer = args.filer_native != "python" and native_volume
        if args.filer_native == "native" and not native_volume:
            raise SystemExit("-filer.native=native needs the native "
                             "volume data plane in-process "
                             "(-dataplane native)")
        if want_native_filer:
            from .filer.native_front import NativeFilerFront

            # python filer app demoted to relay backend on a loopback
            # port; the native front owns the public filer port (the S3
            # gateway below keeps talking to the python app directly —
            # its internal filer calls are query-parameterized and
            # would only relay through the front anyway)
            ft = ServerThread(fs.app, host="127.0.0.1", port=0).start()
            fs.address = ft.address
            threads.append(ft)
            filer_front = NativeFilerFront(
                fs, mt.url, args.filer_port, ft.port, listen_ip=args.ip,
                workers=args.filer_native_workers)
            fs._native_front = filer_front  # keeps the threads alive
            print(f"filer listening on "
                  f"http://{args.ip}:{filer_front.port} (native front; "
                  f"python backend :{ft.port})")
        else:
            ft = ServerThread(fs.app, host=args.ip,
                              port=args.filer_port).start()
            fs.address = ft.address
            threads.append(ft)
            print(f"filer listening on {ft.url}")
        if args.s3:
            import json as _json

            from .s3.server import S3ApiServer

            iam_cfg = None
            if args.s3_config:
                with open(args.s3_config) as f:
                    iam_cfg = _json.load(f)
            s3 = S3ApiServer(ft.url, iam_config=iam_cfg)
            want_native_s3 = args.s3_native != "python" and native_volume
            if args.s3_native == "native" and not native_volume:
                raise SystemExit("-s3.native=native needs the native "
                                 "volume data plane in-process "
                                 "(-dataplane native)")
            if want_native_s3:
                from .s3.native_front import NativeS3Front

                st = ServerThread(s3.app, host="127.0.0.1",
                                  port=0).start()
                threads.append(st)
                front = NativeS3Front(s3, fs.filer, mt.url,
                                      args.s3_port, st.port,
                                      listen_ip=args.ip)
                s3._native_front = front  # keeps the threads alive
                print(f"s3 gateway listening on "
                      f"http://{args.ip}:{front.port} (native front; "
                      f"python backend :{st.port})")
            else:
                st = ServerThread(s3.app, host=args.ip,
                                  port=args.s3_port).start()
                threads.append(st)
                print(f"s3 gateway listening on {st.url}")
    return threads


def _run_benchmark(args) -> int:
    """weed benchmark equivalent (command/benchmark.go:111): concurrent
    write then read with latency percentiles."""
    import threading
    import time

    import numpy as np
    import requests

    from .operation import verbs

    if getattr(args, "target", "fid") in ("s3", "filer"):
        return _run_benchmark_gateway(args)
    n, size, conc = args.n, args.size, args.concurrency
    if getattr(args, "client", "python") == "native":
        return _run_benchmark_native(args)
    payload_rng = np.random.default_rng(0)
    payload = payload_rng.bytes(size)
    fids: list[str] = []
    fid_lock = threading.Lock()
    write_lat: list[float] = []
    read_lat: list[float] = []
    err = [0]

    from .rpc.httpclient import session as _pooled

    def writer(count):
        sess = _pooled()
        done = 0
        while done < count:
            # one assign hands out a run of fids (fid, fid_1, ...) —
            # the master round trip amortizes over the whole batch
            # (the reference benchmark rides -b the same way)
            batch = min(100, count - done)
            try:
                a = verbs.assign(args.master, count=batch,
                                 collection=args.collection)
            except Exception:
                err[0] += batch  # every planned write in the batch failed
                done += batch
                continue
            for i in range(batch):
                fid = a.fid if i == 0 else f"{a.fid}_{i}"
                t0 = time.perf_counter()
                try:
                    sess.post(f"http://{a.url}/{fid}", data=payload,
                              timeout=30)
                    with fid_lock:
                        fids.append(fid)
                        write_lat.append(time.perf_counter() - t0)
                except Exception:
                    err[0] += 1
            done += batch

    def reader(my_fids):
        from .wdclient.client import MasterClient

        mc = MasterClient(args.master)
        sess = _pooled()
        for fid in my_fids:
            t0 = time.perf_counter()
            try:
                resp = sess.get(mc.lookup_file_id(fid), timeout=30)
                assert len(resp.content) == size
                with fid_lock:
                    read_lat.append(time.perf_counter() - t0)
            except Exception:
                err[0] += 1

    def run_phase(name, fn, work):
        threads = [threading.Thread(target=fn, args=(w,)) for w in work]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        return dt

    per = [n // conc + (1 if i < n % conc else 0) for i in range(conc)]
    wdt = run_phase("write", writer, per)
    chunks = [fids[i::conc] for i in range(conc)]
    rdt = run_phase("read", reader, chunks)

    def pct(lat, p):
        return sorted(lat)[int(len(lat) * p / 100)] * 1000 if lat else 0

    out = {
        "write_rps": round(len(write_lat) / wdt, 1),
        "write_mbps": round(len(write_lat) * size / wdt / 1e6, 2),
        "write_p50_ms": round(pct(write_lat, 50), 2),
        "write_p99_ms": round(pct(write_lat, 99), 2),
        "read_rps": round(len(read_lat) / rdt, 1),
        "read_mbps": round(len(read_lat) * size / rdt / 1e6, 2),
        "read_p50_ms": round(pct(read_lat, 50), 2),
        "read_p99_ms": round(pct(read_lat, 99), 2),
        "errors": err[0],
    }
    print(json.dumps(out, indent=2))
    return 0


def _run_benchmark_gateway(args) -> int:
    """Gateway-path benchmark: PUT+GET through the S3 server (SigV4
    auth -> filer autochunk -> assign -> volume) or the bare filer
    HTTP path. Requests are pre-built (and pre-signed) in Python, then
    replayed by the native keep-alive client (dp_bench_raw) so the
    measurement is the SERVER, not a GIL-bound load generator.
    Reference path: s3api_object_handlers_put.go ->
    filer_server_handlers_write_autochunk.go:25."""
    import time
    import urllib.parse

    import numpy as np
    import requests

    from .native import dataplane as dpmod

    if not dpmod.available():
        raise SystemExit("gateway benchmark needs the native client "
                         "(g++ / prebuilt libseaweed_dataplane.so)")
    n, size, conc = args.n, args.size, args.concurrency
    payload = bytes(ord("a") + (i * 31 + 7) % 26 for i in range(size))
    is_s3 = args.target == "s3"
    base = (args.s3_url if is_s3 else args.filer_url).rstrip("/")
    parts = urllib.parse.urlsplit(base)
    host, _, port = parts.netloc.partition(":")

    def build(method: str, path: str, body: bytes) -> bytes:
        url = f"{base}{path}"
        headers = {"Host": parts.netloc,
                   "Content-Length": str(len(body))}
        if body:
            headers["Content-Type"] = "application/octet-stream"
        if is_s3 and args.s3_access:
            from .s3.sigv4_client import sign_headers
            headers.update(sign_headers(method, url, args.s3_access,
                                        args.s3_secret, body))
        head = f"{method} {path} HTTP/1.1\r\n" + "".join(
            f"{k}: {v}\r\n" for k, v in headers.items()) + "\r\n"
        return head.encode() + body

    prefix = f"/{args.bucket}/bench" if is_s3 else "/bench"
    if is_s3:
        # the bucket must exist before objects land in it
        from .s3.sigv4_client import sign_headers
        h = {}
        if args.s3_access:
            h = sign_headers("PUT", f"{base}/{args.bucket}",
                             args.s3_access, args.s3_secret)
        session().put(f"{base}/{args.bucket}", headers=h, timeout=10)

    t0 = time.perf_counter()
    puts = [build("PUT", f"{prefix}/{i:07d}", payload) for i in range(n)]
    gets = [build("GET", f"{prefix}/{i:07d}", b"") for i in range(n)]
    sign_s = time.perf_counter() - t0

    def pct(lat, p):
        return float(np.percentile(lat, p)) * 1000 if len(lat) else 0

    wwall, wlat, werr = dpmod.bench_raw(host, int(port or 80), puts, conc)
    rwall, rlat, rerr = dpmod.bench_raw(host, int(port or 80), gets, conc)
    wlat, rlat = wlat[wlat > 0], rlat[rlat > 0]
    out = {
        "target": args.target,
        "client": "native-raw",
        "signing": bool(is_s3 and args.s3_access),
        "sign_build_s": round(sign_s, 2),
        "write_rps": round((n - werr) / wwall, 1),
        "write_mbps": round((n - werr) * size / wwall / 1e6, 2),
        "write_p50_ms": round(pct(wlat, 50), 2),
        "write_p99_ms": round(pct(wlat, 99), 2),
        "read_rps": round((n - rerr) / rwall, 1),
        "read_mbps": round((n - rerr) * size / rwall / 1e6, 2),
        "read_p50_ms": round(pct(rlat, 50), 2),
        "read_p99_ms": round(pct(rlat, 99), 2),
        "errors": werr + rerr,
    }
    print(json.dumps(out, indent=2))
    return 0


def _run_benchmark_native(args) -> int:
    """Benchmark with the C++ load generator: Python only assigns fids
    (batched) and aggregates; every timed request is native."""
    import numpy as np

    from .native import dataplane as dpmod
    from .operation import verbs

    import time

    n, size, conc = args.n, args.size, args.concurrency
    if getattr(args, "replication", ""):
        # replicated volumes fan out natively only after the control
        # plane pushes peer lists (~2s refresh): wait for a warmup
        # write to land on the native path BEFORE minting the measured
        # fids — their 10s jwt window must not be spent waiting here.
        # repl_post is a lifetime counter: gate on its DELTA, not its
        # value, or a previous run's fan-outs would satisfy the check
        def _repl_post(url):
            st = session().get(f"http://{url}/status", timeout=5).json()
            nd = st.get("native_dataplane")
            return None if nd is None else nd.get("repl_post", 0)

        base: dict[str, int | None] = {}
        deadline = time.time() + 20
        while time.time() < deadline:
            a = verbs.assign(args.master, collection=args.collection,
                             replication=args.replication)
            if a.url not in base:
                base[a.url] = _repl_post(a.url)
            verbs.upload(a, b"warmup")
            now_ct = _repl_post(a.url)
            if now_ct is None or now_ct > (base[a.url] or 0):
                break  # native fan-out live (or pure-python server)
            time.sleep(0.5)

    by_url: dict[str, tuple[list[str], list[str]]] = {}
    left = n
    while left > 0:
        batch = min(1000, left)
        a = verbs.assign(args.master, count=batch,
                         collection=args.collection,
                         replication=getattr(args, "replication", ""))
        fids, auths = by_url.setdefault(a.url, ([], []))
        fids.append(a.fid)
        fids.extend(f"{a.fid}_{i}" for i in range(1, batch))
        # batch slots share the base fid's token
        # (volume_server_handlers.go:181 strips the _N suffix)
        auths.extend([a.auth] * batch)
        left -= batch

    def run(mode: str) -> tuple[float, list, int, int]:
        total_wall, lats, errs, count = 0.0, [], 0, 0
        for url, (fids, auths) in by_url.items():
            host, _, port = url.partition(":")
            wall, lat, err = dpmod.bench(
                host, int(port), mode, fids, size, conc,
                auths=auths if any(auths) else None)
            total_wall += wall
            lats.append(lat[lat > 0])
            errs += err
            count += len(fids) - err
        return total_wall, np.concatenate(lats), errs, count

    wwall, wlat, werr, wcount = run("post")
    rwall, rlat, rerr, rcount = run("get")

    def pct(lat, p):
        return float(np.percentile(lat, p)) * 1000 if len(lat) else 0

    out = {
        "client": "native",
        "write_rps": round(wcount / wwall, 1),
        "write_mbps": round(wcount * size / wwall / 1e6, 2),
        "write_p50_ms": round(pct(wlat, 50), 2),
        "write_p99_ms": round(pct(wlat, 99), 2),
        "read_rps": round(rcount / rwall, 1),
        "read_mbps": round(rcount * size / rwall / 1e6, 2),
        "read_p50_ms": round(pct(rlat, 50), 2),
        "read_p99_ms": round(pct(rlat, 99), 2),
        "errors": werr + rerr,
    }
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())


def _run_filer_copy(args) -> int:
    """Upload local files/directories into a filer directory
    (command/filer_copy.go). Directories recurse; the destination is
    always treated as a directory."""
    import os

    import requests

    filer = args.filer.rstrip("/")
    dest = "/" + args.dest.strip("/")
    params = {}
    if args.collection:
        params["collection"] = args.collection
    if args.max_mb:
        params["maxMB"] = str(args.max_mb)
    uploaded = 0
    for src in args.sources:
        if os.path.isdir(src):
            base = os.path.basename(os.path.abspath(src))
            for dirpath, _, files in os.walk(src):
                rel = os.path.relpath(dirpath, src)
                for f in sorted(files):
                    target = "/".join(
                        p for p in (dest, base,
                                    "" if rel == "." else rel, f) if p)
                    with open(os.path.join(dirpath, f), "rb") as fh:
                        r = session().post(f"{filer}/{target.lstrip('/')}",
                                          params=params, data=fh,
                                          timeout=600)
                    if r.status_code >= 300:
                        print(f"{target}: {r.text}")
                        return 1
                    uploaded += 1
                    print(f"{os.path.join(dirpath, f)} -> /{target.lstrip('/')}")
        else:
            target = f"{dest}/{os.path.basename(src)}"
            with open(src, "rb") as fh:
                r = session().post(f"{filer}{target}", params=params,
                                  data=fh, timeout=600)
            if r.status_code >= 300:
                print(f"{target}: {r.text}")
                return 1
            uploaded += 1
            print(f"{src} -> {target}")
    print(f"copied {uploaded} files")
    return 0
