"""Positive + negative controls for the new analyzer rules: each rule
must fire on a minimal synthetic violation and stay silent on the
sanctioned shape of the same code."""
import pytest

from seaweedfs_tpu.analysis.engine import Engine

pytestmark = pytest.mark.lint


def _run(tmp_path, files: dict, rules=None):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return Engine(roots=[str(tmp_path)], rule_names=rules,
                  baseline_path=None, repo_root=str(tmp_path)).execute()


# -- lock-discipline ----------------------------------------------------

def test_lock_bare_acquire_fires_and_try_finally_passes(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "class S:\n"
        "    def bad(self):\n"
        "        self._lock.acquire()\n"
        "        self.n += 1\n"
        "        self._lock.release()\n"
        "    def good(self):\n"
        "        self._lock.acquire()\n"
        "        try:\n"
        "            self.n += 1\n"
        "        finally:\n"
        "            self._lock.release()\n"
    )}, rules=["lock-discipline"])
    assert [f.line for f in run.by_rule("lock-discipline")] == [3]


def test_lock_wrapper_methods_exempt(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/utils/a.py": (
        "class Guard:\n"
        "    def __enter__(self):\n"
        "        self._lock.acquire()\n"
        "        return self\n"
    )}, rules=["lock-discipline"])
    assert not run.findings


def test_blocking_call_under_lock_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "import time\n"
        "class S:\n"
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)\n"
        "    def good(self):\n"
        "        with self._lock:\n"
        "            self.n += 1\n"
        "        time.sleep(0.1)\n"
    )}, rules=["lock-discipline"])
    assert [f.line for f in run.by_rule("lock-discipline")] == [5]


def test_condition_wait_and_nested_def_exempt_under_lock(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "import time\n"
        "class S:\n"
        "    def ok(self):\n"
        "        with self._cv:\n"
        "            self._cv.wait(1.0)\n"
        "    def ok2(self):\n"
        "        with self._lock:\n"
        "            def worker():\n"
        "                time.sleep(1)\n"
        "            self.w = worker\n"
    )}, rules=["lock-discipline"])
    assert not run.findings


def test_lock_order_inversion_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "class S:\n"
        "    def bad(self):\n"
        "        with self._hardlink_lock:\n"
        "            with self._mutation_lock:\n"
        "                pass\n"
        "    def good(self):\n"
        "        with self._mutation_lock:\n"
        "            with self._hardlink_lock:\n"
        "                pass\n"
    )}, rules=["lock-discipline"])
    findings = run.by_rule("lock-discipline")
    assert [f.line for f in findings if "inversion" in f.message] == [4]


def test_commit_fsync_under_lock_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/storage/commit.py": (
        "import os\n"
        "class S:\n"
        "    def bad(self):\n"
        "        with self._cond:\n"
        "            os.fsync(self.fd)\n"
        "    def good(self):\n"
        "        with self._cond:\n"
        "            batch = list(self._q)\n"
        "        os.fsync(self.fd)\n"
    )}, rules=["lock-discipline"])
    findings = run.by_rule("lock-discipline")
    assert [f.line for f in findings if "fsync" in f.message] == [5]


def test_commit_fsync_outside_commit_py_allowed(tmp_path):
    # the contract is scoped to the group-commit scheduler: a volume's
    # own sync-under-lock elsewhere is contract 2's business (fsync is
    # not in BLOCKING — direct IO is allowed under the write lock)
    run = _run(tmp_path, {"seaweedfs_tpu/storage/other.py": (
        "import os\n"
        "class S:\n"
        "    def ok(self):\n"
        "        with self._cond:\n"
        "            os.fsync(self.fd)\n"
    )}, rules=["lock-discipline"])
    assert not [f for f in run.by_rule("lock-discipline")
                if "fsync" in f.message]


# -- async-hygiene ------------------------------------------------------

def test_async_blocking_calls_fire(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/s3/a.py": (
        "import time\n"
        "from ..rpc.httpclient import session\n"
        "async def handle_get(req):\n"
        "    time.sleep(1)\n"
        "    r = session().get('http://x', timeout=5)\n"
        "    return r\n"
    )}, rules=["async-hygiene"])
    assert [f.line for f in run.by_rule("async-hygiene")] == [4, 5]


def test_async_nested_sync_def_is_off_loop(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/s3/a.py": (
        "import asyncio, time\n"
        "async def handle_get(req):\n"
        "    def worker():\n"
        "        time.sleep(1)\n"
        "    await asyncio.to_thread(worker)\n"
    )}, rules=["async-hygiene"])
    assert not run.findings


# -- context-propagation ------------------------------------------------

def test_submit_without_copy_context_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "import contextvars\n"
        "def kick(pool, fn):\n"
        "    pool.submit(fn)\n"
        "def kick_ok(pool, fn):\n"
        "    pool.submit(contextvars.copy_context().run, fn)\n"
    )}, rules=["context-propagation"])
    assert [f.line for f in run.by_rule("context-propagation")] == [3]


def test_bare_web_application_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/server/a.py": (
        "from aiohttp import web\n"
        "from ..utils import retry\n"
        "def bad():\n"
        "    return web.Application()\n"
        "def good():\n"
        "    return web.Application(\n"
        "        middlewares=[retry.aiohttp_middleware('x')])\n"
    )}, rules=["context-propagation"])
    assert [f.line for f in run.by_rule("context-propagation")] == [4]


def test_untraced_dirs_out_of_scope(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/ops/a.py": (
        "def kick(pool, fn):\n"
        "    pool.submit(fn)\n"
    )}, rules=["context-propagation"])
    assert not run.findings


def test_commit_scheduler_submit_allowed(tmp_path):
    # CommitScheduler.submit enqueues data, not a callable — no user
    # code crosses the thread hop, so no copy_context is needed
    run = _run(tmp_path, {"seaweedfs_tpu/server/a.py": (
        "async def _write_fid(self, v, n):\n"
        "    ticket = self.commit.submit(v, len(n))\n"
        "    await ticket\n"
    )}, rules=["context-propagation"])
    assert not run.findings


# -- resource-safety ----------------------------------------------------

def test_unclosed_stream_fires_with_and_finally_pass(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "from ..rpc.httpclient import session\n"
        "def bad(url):\n"
        "    r = session().get(url, stream=True, timeout=5)\n"
        "    return r.content\n"
        "def good_with(url):\n"
        "    with session().get(url, stream=True, timeout=5) as r:\n"
        "        return r.content\n"
        "def good_finally(url):\n"
        "    r = session().get(url, stream=True, timeout=5)\n"
        "    try:\n"
        "        return r.content\n"
        "    finally:\n"
        "        r.close()\n"
    )}, rules=["resource-safety"])
    assert [f.line for f in run.by_rule("resource-safety")] == [3]


def test_socket_escaping_to_owner_passes(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/filer/a.py": (
        "import socket\n"
        "class C:\n"
        "    def connect(self):\n"
        "        s = socket.create_connection(('h', 1), timeout=2)\n"
        "        self._sock = s\n"
        "    def leak(self):\n"
        "        s = socket.create_connection(('h', 1), timeout=2)\n"
        "        s.sendall(b'x')\n"
    )}, rules=["resource-safety"])
    assert [f.line for f in run.by_rule("resource-safety")] == [7]


# -- jax-hygiene --------------------------------------------------------

def test_sync_in_jitted_function_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/ops/extra.py": (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def bad(x):\n"
        "    return np.asarray(x)\n"
        "@jax.jit\n"
        "def good(x):\n"
        "    return x + 1\n"
    )}, rules=["jax-hygiene"])
    assert [f.line for f in run.by_rule("jax-hygiene")] == [5]


def test_feed_sync_outside_drain_site_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/ops/codec_pallas.py": (
        "def submit_path(dev):\n"
        "    dev.block_until_ready()\n"
        "def drain(fut):\n"
        "    d = fut.result()\n"
        "    d.block_until_ready()\n"
        "    return d\n"
    )}, rules=["jax-hygiene"])
    assert [f.line for f in run.by_rule("jax-hygiene")] == [2]


def test_sync_in_non_feed_module_not_flagged(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/ops/other.py": (
        "def anywhere(dev):\n"
        "    dev.block_until_ready()\n"
    )}, rules=["jax-hygiene"])
    assert not run.findings


# -- dp-faults (native text rule) ---------------------------------------

_CC_OK = (
    "// fault gate\n"
    "bool gate_request(Conn* c) {\n"
    "  if (delay > 0) usleep(100);\n"
    "  return false;\n"
    "}\n"
)

_CC_BAD = _CC_OK + (
    "void elsewhere() {\n"
    "  usleep(100);\n"
    "}\n"
)


def test_sleep_outside_fault_gate_fires(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/native/dataplane.cc": _CC_BAD},
               rules=["dp-faults"])
    assert [f.line for f in run.by_rule("dp-faults")] == [7]


def test_sleep_inside_fault_gate_passes(tmp_path):
    run = _run(tmp_path, {"seaweedfs_tpu/native/dataplane.cc": _CC_OK},
               rules=["dp-faults"])
    assert not run.findings
    assert run.stats["dp_sleep_sites"] == 1


def test_new_front_stats_needs_delete(tmp_path):
    bad = "void f() {\n  auto* s = new FrontStats;\n}\n"
    good = ("void f() {\n  auto* s = new FrontStats;\n"
            "  delete s;\n}\n")
    run = _run(tmp_path, {"seaweedfs_tpu/native/dataplane.cc": bad},
               rules=["dp-faults"])
    assert [f.line for f in run.by_rule("dp-faults")] == [2]
    run2 = _run(tmp_path, {"seaweedfs_tpu/native/dataplane.cc": good},
                rules=["dp-faults"])
    assert not run2.findings
