"""The deployment a configuration states, brought up in this process:
one master and N volume servers (server/cluster.py Cluster), each in a
rack of its own, every one with the configuration's codec backend, and
the shell's commands driven against it. Also the counters the cell
reads from the servers' /metrics, and JAX's compile events.

The codec router's probe curve (ec/probe.py, `-ec.backend=auto`) is
kept in the run's own work directory: an `auto` run sweeps in its own
set-up, and no run, whatever its backend, reads a curve another left.
"""
from __future__ import annotations

import os
import re
import time


# the single-chip device codec: the one `-ec.backend=auto` hands out on
# a one-chip host where its measured feed beats the CPU codec
# (ec/backend.py DEVICE_BACKENDS, ec/probe.py run_sweep)
AUTO_DEVICE_BACKEND = "pallas"
PROBE_CACHE_ENV = "SEAWEEDFS_TPU_EC_PROBE_CACHE"


class BenchError(Exception):
    pass


def wait_for(fn, what: str, timeout: float = 60.0, step: float = 0.02):
    deadline = time.monotonic() + timeout
    while True:
        got = fn()
        if got:
            return got
        if time.monotonic() >= deadline:
            raise BenchError(f"timed out waiting for {what}")
        time.sleep(step)


class Compiles:
    """XLA compilations and persistent-cache hits, from JAX's
    monitoring events (copied from chip_smoke.py)."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = {"xla_compiles": 0, "cache_hits": 0, "cache_misses": 0}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in self.n:
            self.n[name] += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["xla_compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


_LINE = re.compile(r'^([a-zA-Z_:][\w:]*)(\{(.*)\})? (\S+)$')


def parse_metrics(text: str) -> dict:
    """{(name, ((label, value), ...)): value} of a Prometheus text
    exposition, buckets left out."""
    out = {}
    for line in text.splitlines():
        m = _LINE.match(line)
        if not m or m.group(1).endswith("_bucket"):
            continue
        labels = tuple(sorted(re.findall(r'(\w+)="([^"]*)"',
                                         m.group(3) or "")))
        out[(m.group(1), labels)] = float(m.group(4))
    return out


def total(counters: dict, name: str, **labels) -> float:
    """Sum of a series over the label sets that match `labels`."""
    want = set(labels.items())
    return sum(v for (n, lab), v in counters.items()
               if n == name and want <= set(lab))


def device_backend(config: dict) -> str:
    """The codec label under which the configuration's device coding is
    counted: its `ec_backend`, or for `auto` the single-chip device
    codec, the name the router records when it picks the device."""
    name = config["ec_backend"]
    return AUTO_DEVICE_BACKEND if name == "auto" else name


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Deployment:
    """Master + volume servers over `work`, every server in its own
    rack; volumes whose files sit in a server's directory before start
    are loaded by it."""

    def __init__(self, work: str, config: dict):
        self.work = work
        self.config = config
        self.n = config["volume_servers"]
        self.dirs = [os.path.join(work, f"vol{i}_0") for i in range(self.n)]
        for d in self.dirs:
            os.makedirs(d, exist_ok=True)
        self.probe_cache = os.path.join(work, "probe", "ec_probe.json")
        os.environ[PROBE_CACHE_ENV] = self.probe_cache
        self.cluster = None
        self.env = None

    def start(self, max_volumes: int) -> None:
        from seaweedfs_tpu.server.cluster import Cluster
        from seaweedfs_tpu.shell.env import CommandEnv

        racks = self.config["racks"]
        self.cluster = Cluster(
            self.work, n_volume_servers=self.n, max_volumes=max_volumes,
            volume_size_limit=self.config["volume_size_limit_mb"] << 20,
            ec_backend=self.config["ec_backend"],
            topology=[("dc1", f"rack{i % racks}") for i in range(self.n)])
        self.urls = [self.cluster.volume_url(i).split("//", 1)[1]
                     for i in range(self.n)]
        self.env = CommandEnv(self.cluster.master_url)
        self.env.acquire_lock()

    def stop(self) -> None:
        if self.env is not None:
            env, self.env = self.env, None
            try:
                env.release_lock()
            finally:
                env.close()
        if self.cluster is not None:
            self.cluster.stop()
            self.cluster = None

    # -- counters --------------------------------------------------------
    def scrape(self) -> dict:
        """The process's counters. Every server runs in this process
        and exposes the same registry, so one server's /metrics is the
        whole deployment's."""
        from seaweedfs_tpu.rpc.httpclient import session

        text = session().get(f"http://{self.urls[0]}/metrics",
                             timeout=30).text
        return parse_metrics(text)

    # -- volumes ---------------------------------------------------------
    def wait_volumes(self, vids) -> None:
        want = set(vids)

        def seen():
            have = set()
            for node in self.env.data_nodes():
                have.update(int(v) for v in node.get("volumes", []))
            return want <= have
        wait_for(seen, f"volumes {sorted(want)} to register", 120)

    def seal(self, vid: int) -> dict:
        from seaweedfs_tpu.shell.repl import run_command

        return run_command(self.env, f"ec.encode -volumeId={vid} "
                                     f"-codec={self.config['code']['spec']}")

    def holders(self, vid: int) -> dict[int, list[str]]:
        return self.env.ec_full_info(vid)[2]

    def lose(self, vid: int, sids: list[int]) -> None:
        """Delete shards as a lost disk would, then wait until the
        master no longer lists them (the heartbeat's part, outside any
        timed interval)."""
        holders = self.holders(vid)
        by_url: dict[str, list[int]] = {}
        for s in sids:
            for url in holders.get(s, []):
                by_url.setdefault(url, []).append(s)
        for url, ss in by_url.items():
            self.env.vs_post(url, "/admin/ec/delete",
                             {"volume": vid, "shard_ids": ss})
        wait_for(lambda: not set(sids) & set(self.holders(vid)),
                 f"the master to see shards {sids} of {vid} gone")

    def rebuild(self, vid: int) -> dict:
        from seaweedfs_tpu.shell import commands_ec

        return commands_ec.ec_rebuild(self.env, vid)

    def shard_path(self, vid: int, sid: int, url: str) -> str:
        from seaweedfs_tpu.ec import geometry as geo

        return os.path.join(self.dirs[self.urls.index(url)],
                            str(vid) + geo.shard_ext(sid))

    def unmounted(self, vid: int, total: int) -> list[int]:
        """Shards of `vid` that the master does not list, or that the
        server it lists does not hold mounted, or whose file is gone."""
        holders = self.holders(vid)
        bad = []
        for sid in range(total):
            urls = holders.get(sid, [])
            ok = bool(urls)
            for url in urls:
                store = self.cluster.stores[self.urls.index(url)]
                ecv = store.ec_volumes.get(vid)
                ok = ok and ecv is not None and sid in ecv.shards and \
                    os.path.exists(self.shard_path(vid, sid, url))
            if not ok:
                bad.append(sid)
        return bad
