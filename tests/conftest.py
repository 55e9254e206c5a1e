"""Test bootstrap: the CPU backend with 8 virtual devices, before any
jax use.

The tests run where there is no chip; sharding is tested on a virtual
CPU mesh. JAX_PLATFORMS=cpu also tells the device codecs that the CPU
is asked for on purpose (seaweedfs_tpu/ops/device.py).
"""
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_sessionstart(session):
    assert jax.devices()[0].platform == "cpu", jax.devices()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test, excluded from the tier-1 gate "
        "(pytest -m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / process-kill robustness test "
        "(select the whole family with pytest -m chaos)")
    config.addinivalue_line(
        "markers",
        "mesh: multi-device mesh-codec test; skips itself on hosts "
        "where fewer than 2 jax devices are visible (CI runs them on "
        "the 8-device virtual CPU mesh this conftest forces)")
    config.addinivalue_line(
        "markers",
        "rackloss: whole-rack-kill chaos scenario (placement-aware, "
        "bandwidth-shaped repair); selectable/excludable like chaos")
    config.addinivalue_line(
        "markers",
        "tier: tiered-storage lifecycle test (hot -> warm EC -> cold "
        "remote); selectable with pytest -m tier")
    config.addinivalue_line(
        "markers",
        "lint: static-analysis gate (seaweedfs_tpu/analysis/); "
        "pytest -m lint runs the whole analyzer in one engine pass")
    config.addinivalue_line(
        "markers",
        "sanitize: rebuilds the native data plane under ASan/TSan and "
        "re-runs the parity + concurrency suites in a subprocess; "
        "slow, needs gcc + libasan/libtsan")
    config.addinivalue_line(
        "markers",
        "codes: pluggable erasure-code family tests (LRC beside RS, "
        "repair plans, bit-plane kernel scheduling); selectable with "
        "pytest -m codes")
    config.addinivalue_line(
        "markers",
        "durability: write-path durability-contract tests (group "
        "commit, ack ordering, X-Sw-Durability headers, "
        "crash-consistency); selectable with pytest -m durability")


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _isolate_process_globals():
    """Reset process-wide registries between test modules so modules
    can't leak state into each other (the round-1 order-dependent
    TestMountFlow failure): the thread-local keep-alive HTTP sessions
    (a pooled connection to a dead server's reused ephemeral port
    surfaces as a ConnectionError in a later module) and the tier
    backend-storage registry configured by configure_storage()."""
    from seaweedfs_tpu.rpc import httpclient
    from seaweedfs_tpu.storage import backend as bk

    storages_before = dict(bk._storages)
    yield
    bk._storages.clear()
    bk._storages.update(storages_before)
    s = getattr(httpclient._local, "session", None)
    if s is not None:
        s.close()
        httpclient._local.session = None
