"""Distributed tracing: traceparent round-trip, ring-buffer eviction,
span parentage, slow-request logging, and end-to-end propagation
S3 PUT -> filer -> volume inside one trace id (utils/tracing.py)."""
import time

import pytest
import requests

from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.utils import glog, metrics, tracing


@pytest.fixture
def trace_config():
    """Snapshot/restore tracing knobs + ring so tests don't leak."""
    slow, size = tracing._slow_threshold, tracing._buffer_size
    yield
    tracing.configure(slow_threshold=slow, buffer_size=size)
    tracing.reset()


class TestTraceparent:
    def test_roundtrip(self):
        ctx = tracing.TraceContext(tracing.new_trace_id(),
                                   tracing.new_span_id())
        parsed = tracing.parse_traceparent(tracing.format_traceparent(ctx))
        assert parsed is not None
        assert parsed.trace_id == ctx.trace_id
        assert parsed.span_id == ctx.span_id
        assert parsed.flags == ctx.flags

    def test_parse_valid_header(self):
        h = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
        ctx = tracing.parse_traceparent(h)
        assert ctx is not None
        assert ctx.trace_id == "0af7651916cd43dd8448eb211c80319c"
        assert ctx.span_id == "b7ad6b7169203331"
        assert tracing.format_traceparent(ctx) == h

    @pytest.mark.parametrize("bad", [
        None,
        "",
        "garbage",
        "00-abc-def-01",                                    # wrong lengths
        "00-" + "0" * 32 + "-b7ad6b7169203331-01",          # zero trace
        "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",
        "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",
        "00-0af7651916cd43dd8448eb211c80319X-b7ad6b7169203331-01",
        "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01-extra",
    ])
    def test_parse_rejects_malformed(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_parse_accepts_future_version_extra_fields(self):
        # per W3C, unknown (non-ff) versions may append fields
        h = "01-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01-x"
        assert tracing.parse_traceparent(h) is not None


class TestSpans:
    def test_nesting_and_remote_parent(self, trace_config):
        remote = tracing.TraceContext(tracing.new_trace_id(),
                                      tracing.new_span_id())
        with tracing.span("srv", service="s3", kind="server",
                          remote=remote) as root:
            assert tracing.current_traceparent() != ""
            with tracing.span("hop", kind="client") as child:
                pass
        assert root["trace_id"] == remote.trace_id
        assert root["parent_id"] == remote.span_id
        assert child["trace_id"] == remote.trace_id
        assert child["parent_id"] == root["span_id"]
        # context is restored after the with-block
        assert tracing.current() is None

    def test_ring_eviction(self, trace_config):
        tracing.reset()
        tracing.configure(buffer_size=4)
        for n in range(10):
            with tracing.span(f"s{n}"):
                pass
        names = [s["name"] for s in tracing._spans]
        assert names == ["s6", "s7", "s8", "s9"]
        # growing the buffer keeps what survived
        tracing.configure(buffer_size=8)
        assert [s["name"] for s in tracing._spans] == names

    def test_error_status_and_server_histogram(self, trace_config):
        with pytest.raises(RuntimeError):
            with tracing.span("boom", service="t", kind="server"):
                raise RuntimeError("x")
        rec = list(tracing._spans)[-1]
        assert rec["status"] == "error"
        m = metrics.render()
        assert 'request_trace_seconds_count{handler="boom",service="t"}' \
            in m

    def test_slow_root_emits_span_tree_log(self, trace_config,
                                           monkeypatch):
        lines = []
        monkeypatch.setattr(
            glog, "warning", lambda msg, *a: lines.append(msg % a))
        tracing.configure(slow_threshold=0.001)
        with tracing.span("slowone", service="s3", kind="server"):
            with tracing.span("inner", kind="client"):
                time.sleep(0.01)
        slow = [ln for ln in lines if "slow request" in ln]
        assert len(slow) == 1, lines
        assert "slowone" in slow[0] and "inner" in slow[0]
        assert "trace_id=" in slow[0]

    def test_fast_root_does_not_log(self, trace_config, monkeypatch):
        lines = []
        monkeypatch.setattr(tracing.glog, "warning",
                            lambda msg, *a: lines.append(msg % a))
        tracing.configure(slow_threshold=10.0)
        with tracing.span("quick", service="s3", kind="server"):
            pass
        assert not lines


def _span_count(name: str) -> float:
    return metrics._counters.get(
        ("span_seconds_count", (("name", name),)), 0.0)


class TestPhasesAndIntervals:
    def test_internal_span_annotates_profiler_trace(self, trace_config,
                                                    tmp_path):
        import jax
        from jax.profiler import ProfileData

        before = _span_count("test.phase")
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.span("test.phase"):
                with tracing.interval("test.step"):
                    time.sleep(0.002)
            # a server span opens no annotation
            with tracing.span("test.server", service="t", kind="server"):
                pass
        finally:
            jax.profiler.stop_trace()
        path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        names = {e.name for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for e in line.events}
        assert {"swfs.test.phase", "swfs.test.step"} <= names
        assert "swfs.test.server" not in names
        assert _span_count("test.phase") == before + 1
        assert _span_count("test.server") == 0

    def test_interval_observes_and_adds_no_ring_record(self, trace_config):
        tracing.reset()
        before = _span_count("test.chunk")
        with tracing.span("test.job"):
            for _ in range(5):
                with tracing.interval("test.chunk") as iv:
                    time.sleep(0.001)
                assert iv.seconds >= 0.001
            with tracing.interval("test.quiet", observe=False):
                pass
        assert [s["name"] for s in tracing._spans] == ["test.job"]
        assert _span_count("test.chunk") == before + 5
        assert _span_count("test.quiet") == 0
        assert 'span_seconds_count{name="test.chunk"}' in metrics.render()

    def test_importing_tracing_does_not_import_jax(self):
        import subprocess
        import sys

        code = ("import sys\n"
                "from seaweedfs_tpu.utils import tracing\n"
                "with tracing.span('p'):\n"
                "    with tracing.interval('i'):\n"
                "        pass\n"
                "assert not any(m == 'jax' or m.startswith('jax.')\n"
                "               for m in sys.modules), 'jax imported'\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr


class TestClusterPropagation:
    def test_one_trace_spans_s3_filer_volume(self, tmp_path_factory,
                                             trace_config):
        c = Cluster(str(tmp_path_factory.mktemp("trace")),
                    n_volume_servers=1, volume_size_limit=16 << 20,
                    with_filer=True, with_s3=True)
        try:
            requests.put(f"{c.s3_url}/tb")
            requests.put(f"{c.s3_url}/tb/k", data=b"trace me" * 64)
            requests.get(f"{c.s3_url}/tb/k")
            body = requests.get(f"{c.s3_url}/debug/traces",
                                params={"limit": 50}).json()
            traces = body["traces"]
            assert isinstance(body["breakers"], list)
            assert isinstance(traces, list) and traces
            hit = None
            for t in traces:
                services = {s["service"] for s in t["spans"]}
                if {"s3", "filer", "volume"} <= services:
                    hit = t
                    break
            assert hit is not None, traces
            # every span shares the gateway's trace id, and the filer /
            # volume server hops chain to a parent inside the trace
            ids = {s["span_id"] for s in hit["spans"]}
            for s in hit["spans"]:
                assert s["trace_id"] == hit["trace_id"]
                if s["service"] in ("filer", "volume") and \
                        s["kind"] == "server":
                    assert s["parent_id"] in ids
            # the trace endpoint exists on every server
            for url in (c.master_url, c.filer_url, c.volume_url(0)):
                r = requests.get(url + "/debug/traces?limit=1")
                assert r.status_code == 200
                assert isinstance(r.json()["traces"], list)
                assert "breakers" in r.json()
            # and request_trace_seconds is exported with service labels
            m = requests.get(f"{c.s3_url}/metrics").text
            assert 'request_trace_seconds_count{handler="dispatch"' \
                   ',service="s3"}' in m
        finally:
            c.stop()
