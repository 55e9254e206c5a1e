"""The one general generator. A traffic mix is a data file,
benchmark/traffic/<name>.json, of these parameters:

- volumes: how many volumes the deployment holds at set-up; one is
  written from the seed, the others are hard links of it under fresh
  ids, spread over the servers (a seal pool costs no disk);
- sealed: whether set-up erasure-codes them (ec.encode);
- lose: {"kind": "data_shard" | "server", "each": "job" | "run"}: a
  data shard, taken in one fixed order of all data shards, or every
  shard of the server that holds shard 0; before each job, or once in
  set-up;
- jobs: "encode" | "rebuild": a closed loop, one shell job at a time,
  as an operator's script runs them;
- reads: {"rate_per_s", "zipf_theta"}: an open loop of needle GETs of
  volume 1 from a child process, YCSB-C style: zipfian popularity over
  a fixed ranking of the needles, the request multiset fixed by the
  rate and the window, its order drawn from the seed;
- warmup_jobs: throwaway jobs of the cell's own size in set-up.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from .data import LAYOUT_SEED, fid, write_volume
from .deploy import BenchError


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def zipf_requests(n_items: int, n_requests: int, theta: float,
                  seed: int) -> list[int]:
    """The request multiset closest to zipfian(theta) over a fixed
    ranking of the items, in an order drawn from `seed`."""
    rank = np.random.default_rng(LAYOUT_SEED).permutation(n_items)
    p = 1.0 / np.arange(1, n_items + 1) ** theta
    p = p / p.sum()
    want = p * n_requests
    counts = np.floor(want).astype(int)
    short = n_requests - counts.sum()
    counts[np.argsort(counts - want)[:short]] += 1
    reqs = np.repeat(rank, counts)
    return np.random.default_rng(seed).permutation(reqs).tolist()


class Traffic:
    def __init__(self, spec: dict, config: dict, seed: int, work: str):
        self.spec = spec
        self.config = config
        self.seed = seed
        self.work = work
        self.code = config["code"]
        self.k = self.code["k"]
        self.total = self.k + self.code.get("local", 0) + \
            self.code["global"]
        self.jobs: list[dict] = []
        self.reads: list = []
        self.sealed: list[int] = []
        self.snap = os.path.join(work, "snap")
        os.makedirs(self.snap)
        # one fixed order of data-shard losses for every seed: which
        # shard is lost sets how many of the rebuild's reads are local,
        # so a seeded order would change the work from run to run
        self._order = np.random.default_rng(LAYOUT_SEED).permutation(
            self.k).tolist()
        self._next_loss = 0

    # -- set-up ---------------------------------------------------------
    def write_data(self, dep) -> None:
        """Volume 1 from the seed; the rest of the pool as hard links."""
        n = self.spec.get("volumes", 1)
        self.volume = write_volume(dep.dirs[0], 1, self.seed, self.config)
        base = self.volume["base"]
        self.dat_bytes = os.path.getsize(base + ".dat")
        # sealing deletes a volume's .dat; the reference keeps a link
        self.ref_dat = os.path.join(self.work, "ref.dat")
        os.link(base + ".dat", self.ref_dat)
        for vid in range(2, n + 1):
            d = dep.dirs[(vid - 1) % dep.n]
            for ext in (".dat", ".idx"):
                os.link(base + ext, os.path.join(d, f"{vid}{ext}"))
        self.pool = list(range(1, n + 1))

    def setup(self, dep) -> None:
        dep.wait_volumes(self.pool)
        if self.spec.get("sealed"):
            for vid in self.pool:
                dep.seal(vid)
        lose = self.spec.get("lose")
        self.lost: list[int] = []
        if lose and lose["each"] == "run":
            self.lost = self.loss(dep)
            dep.lose(1, self.lost)
        for _ in range(self.spec.get("warmup_jobs", 0)):
            self.job(dep, warm=True)
        if self.spec.get("reads"):
            self._read_plan(dep)

    def loss(self, dep) -> list[int]:
        kind = self.spec["lose"]["kind"]
        if kind == "data_shard":
            sid = self._order[self._next_loss % self.k]
            self._next_loss += 1
            return [sid]
        if kind == "server":
            holders = dep.holders(1)
            url = holders[0][0]
            return sorted(s for s, urls in holders.items() if url in urls)
        raise BenchError(f"unknown loss kind {kind!r}")

    # -- jobs -----------------------------------------------------------
    def job(self, dep, warm: bool = False) -> dict:
        op = self.spec["jobs"]
        rec = {"op": op, "warm": warm}
        if op == "encode":
            if not self.pool:
                raise BenchError("the seal pool ran dry: raise `volumes`")
            vid = self.pool.pop(0)
            rec["vid"] = vid
            with _annotate("encode_job"):
                rec["start"] = time.monotonic()
                dep.seal(vid)
                rec["end"] = time.monotonic()
            rec["dat_bytes"] = self.dat_bytes
            self.sealed.append(vid)
        elif op == "rebuild":
            lost = self.loss(dep)
            with _annotate("lose"):
                dep.lose(1, lost)
            with _annotate("rebuild_job"):
                rec["start"] = time.monotonic()
                out = dep.rebuild(1)
                rec["end"] = time.monotonic()
            rec.update(vid=1, lost=lost, dat_bytes=self.dat_bytes,
                       rebuilt_bytes=out.get("rebuilt_bytes", 0),
                       rebuilt=out.get("rebuilt", []))
            # keep this job's answer for the comparison after the
            # window: a hard link survives the shard's next loss. Every
            # shard the harness deleted is due, whatever the program
            # says it rebuilt
            with _annotate("snapshot"):
                rec["snap"] = self._snapshot(dep, lost, len(self.jobs))
        else:
            raise BenchError(f"unknown job {op!r}")
        self.jobs.append(rec)
        return rec

    def _snapshot(self, dep, sids, tag) -> dict:
        """{shard id: a hard link to its file}; a shard the master does
        not list, or whose file is gone, gets a path that stays absent,
        which the comparison counts as wholly wrong."""
        holders = dep.holders(1)
        out = {}
        for sid in sids:
            dst = os.path.join(self.snap, f"{tag}_{sid}")
            out[sid] = dst
            if sid not in holders:
                continue
            try:
                os.link(dep.shard_path(1, sid, holders[sid][0]), dst)
            except FileNotFoundError:
                pass
        return out

    # -- reads ----------------------------------------------------------
    def _read_plan(self, dep) -> None:
        r = self.spec["reads"]
        live = sorted({u for urls in dep.holders(1).values() for u in urls})
        n = len(self.volume["sizes"])
        self.read_targets = [[live[i % len(live)], fid(1, i + 1)]
                             for i in range(n)]
        self.read_rate = r["rate_per_s"]
        self.read_theta = r["zipf_theta"]
        self.read_connections = r.get("connections", 64)

    def _reads(self, start: float, seconds: float) -> list:
        n = int(round(self.read_rate * seconds))
        plan = {"start": start, "interval_s": 1.0 / self.read_rate,
                "targets": self.read_targets,
                "requests": zipf_requests(len(self.read_targets), n,
                                          self.read_theta, self.seed),
                "timeout_s": 60.0, "connections": self.read_connections}
        self.read_requests = plan["requests"]
        p_plan = os.path.join(self.work, "reads_plan.json")
        p_out = os.path.join(self.work, "reads_out.json")
        with open(p_plan, "w", encoding="utf-8") as f:
            json.dump(plan, f)
        here = os.path.dirname(os.path.abspath(__file__))
        return [subprocess.Popen(
            [sys.executable, os.path.join(here, "loadgen.py"), p_plan,
             p_out]), p_out]

    # -- the window -----------------------------------------------------
    def arm(self, seconds: float) -> float:
        """Set the window's start; a read child starts now and waits
        for it. Returns the start (time.monotonic())."""
        self._reads_proc = None
        if not self.spec.get("reads"):
            self.t0 = time.monotonic()
            return self.t0
        self.t0 = time.monotonic() + 1.5
        self._reads_proc = self._reads(self.t0, seconds)
        return self.t0

    def window(self, dep, seconds: float) -> dict:
        """Run the mix for `seconds` from the armed start; jobs in
        flight at the close run to their end. Returns the window's
        start and end."""
        t0 = self.t0
        time.sleep(max(0.0, t0 - time.monotonic()))
        reads = self._reads_proc
        close = t0 + seconds
        if self.spec.get("jobs"):
            while time.monotonic() < close:
                try:
                    self.job(dep)
                except Exception as e:  # reported, and not correct
                    self.jobs.append({"op": self.spec["jobs"],
                                      "error": f"{type(e).__name__}: {e}"})
                    break
        if reads is not None:
            with _annotate("reads_drain"):
                proc, p_out = reads
                try:
                    proc.wait(timeout=seconds + 120)
                finally:
                    if proc.poll() is None:
                        proc.kill()
                        proc.wait()
                if proc.returncode != 0:
                    raise BenchError(f"load generator exited "
                                     f"{proc.returncode}")
                with open(p_out, encoding="utf-8") as f:
                    self.reads = json.load(f)
        return {"start": t0, "end": time.monotonic()}
