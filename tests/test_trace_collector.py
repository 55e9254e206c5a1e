"""Cluster observability plane: deterministic head sampling, the span
pusher, the master's trace collector + tail-based retention, OTLP/JSON
rendering, and metrics federation (master/collector.py,
rpc/trace_push.py, utils/tracing.py)."""
import random
import time

import pytest
import requests

from seaweedfs_tpu.master.collector import (MAX_SPANS_PER_TRACE,
                                            OTLP_SCOPE, MetricsFederator,
                                            SpanCollector, _family_of,
                                            _inject_instance)
from seaweedfs_tpu.rpc.http import ServerThread
from seaweedfs_tpu.rpc.trace_push import SpanPusher
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.utils import metrics, tracing


def _rec(trace_id=None, span_id=None, parent_id="", service="s3",
         name="op", kind="server", status="200", start=None,
         duration=0.01, peer=""):
    return {
        "trace_id": trace_id or tracing.new_trace_id(),
        "span_id": span_id or tracing.new_span_id(),
        "parent_id": parent_id,
        "service": service,
        "name": name,
        "kind": kind,
        "peer": peer,
        "start": time.time() if start is None else start,
        "duration": duration,
        "status": status,
    }


def _counter(name: str) -> float:
    with metrics._lock:
        return sum(v for (n, _), v in metrics._counters.items()
                   if n == name)


@pytest.fixture
def sample_config():
    """Snapshot/restore the global head-sampling rate."""
    rate = tracing.sample_rate()
    yield
    tracing.configure(sample_rate=rate)


# ---------------------------------------------------------------------
# head sampling
# ---------------------------------------------------------------------


class TestSampler:
    def test_deterministic_across_calls(self):
        rng = random.Random(7)
        ids = ["%032x" % rng.getrandbits(128) for _ in range(64)]
        first = [tracing.sample_decision(t, 0.5) for t in ids]
        again = [tracing.sample_decision(t, 0.5) for t in ids]
        assert first == again

    def test_kept_at_low_rate_kept_at_higher_rate(self):
        # the verdict is a threshold on the id's low bits, so the kept
        # set only grows with the rate — a trace sampled at one hop is
        # sampled at every hop even if rates are skewed upward
        rng = random.Random(11)
        ids = ["%032x" % rng.getrandbits(128) for _ in range(256)]
        low = {t for t in ids if tracing.sample_decision(t, 0.2)}
        high = {t for t in ids if tracing.sample_decision(t, 0.7)}
        assert low <= high

    def test_rate_extremes(self):
        tid = tracing.new_trace_id()
        assert tracing.sample_decision(tid, 1.0) is True
        assert tracing.sample_decision(tid, 0.0) is False

    def test_malformed_id_is_kept(self):
        # losing malformed ids would hide bugs, not traffic
        assert tracing.sample_decision("not-hex-at-all", 0.001) is True
        assert tracing.sample_decision("", 0.001) is True

    def test_fraction_tracks_rate(self):
        rng = random.Random(3)
        ids = ["%032x" % rng.getrandbits(128) for _ in range(4000)]
        kept = sum(tracing.sample_decision(t, 0.5) for t in ids)
        assert 0.42 < kept / len(ids) < 0.58

    def test_configure_clamps(self, sample_config):
        tracing.configure(sample_rate=7.0)
        assert tracing.sample_rate() == 1.0
        tracing.configure(sample_rate=-3.0)
        assert tracing.sample_rate() == 0.0


# ---------------------------------------------------------------------
# collector: stitching + tail-based retention
# ---------------------------------------------------------------------


class TestCollector:
    def test_cross_instance_stitching(self):
        c = SpanCollector(max_traces=64)
        tid = tracing.new_trace_id()
        root = _rec(trace_id=tid, service="s3", name="put_object")
        child = _rec(trace_id=tid, parent_id=root["span_id"],
                     service="filer", name="write", kind="server")
        grand = _rec(trace_id=tid, parent_id=child["span_id"],
                     service="volume", name="needle_write")
        c.add_spans("s3:8333", "s3", [root])
        c.add_spans("vol:8080", "volume", [grand])  # out of order
        c.add_spans("filer:8888", "filer", [child])
        got = c.get_trace(tid)
        assert got is not None and got["spans"] == 3
        assert len(got["tree"]) == 1
        r = got["tree"][0]
        assert r["name"] == "put_object" and r["instance"] == "s3:8333"
        assert r["children"][0]["name"] == "write"
        assert r["children"][0]["children"][0]["name"] == "needle_write"

        summaries = c.list_traces()
        assert summaries[0]["trace_id"] == tid
        assert summaries[0]["services"] == ["filer", "s3", "volume"]
        assert set(summaries[0]["instances"]) == \
            {"s3:8333", "filer:8888", "vol:8080"}
        assert summaries[0]["error"] is False

    def test_tail_retention_pins_error_and_slow(self):
        c = SpanCollector(max_traces=16, slow_threshold=1.0)
        bad = _rec(status="error")
        slow = _rec(duration=5.0)
        c.add_spans("i", "s3", [bad, slow])
        for _ in range(40):
            c.add_spans("i", "s3", [_rec()])
        assert len(c._traces) <= 16
        assert c.get_trace(bad["trace_id"]) is not None
        assert c.get_trace(slow["trace_id"]) is not None
        assert c._evicted > 0
        pinned = [s for s in c.list_traces(limit=16) if s["pinned"]]
        assert {p["trace_id"] for p in pinned} >= \
            {bad["trace_id"], slow["trace_id"]}

    def test_all_pinned_still_bounded(self):
        c = SpanCollector(max_traces=16)
        for _ in range(25):
            c.add_spans("i", "s3", [_rec(status="error")])
        assert len(c._traces) == 16

    def test_runaway_trace_span_cap(self):
        c = SpanCollector(max_traces=64)
        tid = tracing.new_trace_id()
        for _ in range(MAX_SPANS_PER_TRACE + 20):
            c.add_spans("i", "s3", [_rec(trace_id=tid)])
        assert c.get_trace(tid)["spans"] == MAX_SPANS_PER_TRACE

    def test_ignores_junk_spans(self):
        c = SpanCollector(max_traces=64)
        assert c.add_spans("i", "s3", [{"no": "trace_id"},
                                       {"trace_id": ""},
                                       {"trace_id": 42}]) == 0
        assert len(c._traces) == 0

    def test_drain_otlp_pending_waits_for_idle(self):
        c = SpanCollector(max_traces=64)
        r = _rec()
        c.add_spans("i", "s3", [r])
        # freshly-touched traces are deferred so late spans still land
        assert c.drain_otlp_pending(min_idle=60.0) == []
        assert c.drain_otlp_pending(min_idle=0.0) == [r["trace_id"]]
        # drained ids do not come back
        assert c.drain_otlp_pending(min_idle=0.0) == []

    def test_observability_block(self):
        c = SpanCollector(max_traces=64)
        c.add_spans("vol:8080", "volume", [_rec()], dropped=3)
        obs = c.observability()
        assert obs["TraceStoreTraces"] == 1
        assert obs["TraceStoreSpans"] == 1
        st = obs["Pushers"]["vol:8080"]
        assert st["Service"] == "volume"
        assert st["SpansReceived"] == 1 and st["SpansDropped"] == 3
        assert st["PushLagSeconds"] is not None


# ---------------------------------------------------------------------
# OTLP rendering
# ---------------------------------------------------------------------


class TestOtlp:
    def test_shape_and_field_encoding(self):
        c = SpanCollector(max_traces=64)
        tid = tracing.new_trace_id()
        root = _rec(trace_id=tid, service="s3", name="put", start=100.0,
                    duration=0.25, status="201", peer="10.0.0.9")
        child = _rec(trace_id=tid, parent_id=root["span_id"],
                     service="filer", kind="client", status="error")
        c.add_spans("s3:1", "s3", [root])
        c.add_spans("filer:2", "filer", [child])
        doc = c.to_otlp(trace_ids=[tid])
        rs = doc["resourceSpans"]
        assert len(rs) == 2  # one per (service, instance)
        by_service = {}
        for entry in rs:
            attrs = {a["key"]: a["value"]["stringValue"]
                     for a in entry["resource"]["attributes"]}
            assert "service.instance.id" in attrs
            scope = entry["scopeSpans"][0]
            assert scope["scope"]["name"] == OTLP_SCOPE
            by_service[attrs["service.name"]] = scope["spans"]
        s = by_service["s3"][0]
        assert s["traceId"] == tid and len(s["spanId"]) == 16
        assert s["kind"] == 2  # server
        # uint64 nanos are strings per the proto3 JSON mapping
        assert s["startTimeUnixNano"] == str(int(100.0 * 1e9))
        assert int(s["endTimeUnixNano"]) - int(s["startTimeUnixNano"]) \
            == int(0.25 * 1e9)
        assert s["status"] == {"code": 0}
        assert "parentSpanId" not in s
        attrs = {a["key"]: a["value"]["stringValue"]
                 for a in s["attributes"]}
        assert attrs["http.response.status_code"] == "201"
        assert attrs["net.peer.name"] == "10.0.0.9"
        f = by_service["filer"][0]
        assert f["kind"] == 3  # client
        assert f["status"] == {"code": 2}  # error
        assert f["parentSpanId"] == root["span_id"]

    def test_unknown_kind_maps_internal(self):
        c = SpanCollector(max_traces=64)
        r = _rec(kind="mystery")
        c.add_spans("i", "s3", [r])
        doc = c.to_otlp(trace_ids=[r["trace_id"]])
        assert doc["resourceSpans"][0]["scopeSpans"][0]["spans"][0][
            "kind"] == 1

    def test_limit_and_unknown_ids(self):
        c = SpanCollector(max_traces=64)
        for _ in range(5):
            c.add_spans("i", "s3", [_rec()])
        assert len(c.to_otlp(limit=2)["resourceSpans"][0]["scopeSpans"]
                   [0]["spans"]) == 2
        assert c.to_otlp(trace_ids=["f" * 32]) == {"resourceSpans": []}


# ---------------------------------------------------------------------
# metrics federation
# ---------------------------------------------------------------------


class TestFederation:
    def test_inject_instance(self):
        assert _inject_instance('up 1', 'a:1') == 'up{instance="a:1"} 1'
        assert _inject_instance('req_total{code="200"} 5', 'a:1') == \
            'req_total{instance="a:1",code="200"} 5'
        # nested federation: already-labeled series pass through
        line = 'up{instance="b:2"} 1'
        assert _inject_instance(line, 'a:1') == line
        assert _inject_instance('junk{unterminated 1', 'a:1') is None
        assert _inject_instance('lonely', 'a:1') is None

    def test_family_of_folds_histogram_components(self):
        assert _family_of('lat_seconds_bucket{le="1"} 3') == "lat_seconds"
        assert _family_of("lat_seconds_sum 1.5") == "lat_seconds"
        assert _family_of("lat_seconds_count 3") == "lat_seconds"
        assert _family_of('req_total{code="200"} 5') == "req_total"

    def test_merged_dedupes_type_lines(self):
        fed = MetricsFederator(master=None)
        text = ("# TYPE req_total counter\n"
                'req_total{code="200"} 5\n')
        now = time.time()
        fed._scraped = {
            "a:1": {"text": text, "ts": now, "error": ""},
            "b:2": {"text": text, "ts": now, "error": ""},
        }
        out = fed.merged()
        assert out.count("# TYPE req_total counter") == 1
        assert 'req_total{instance="a:1",code="200"} 5' in out
        assert 'req_total{instance="b:2",code="200"} 5' in out
        # staleness gauges land in the live registry per instance
        with metrics._lock:
            keys = {k for k in metrics._gauges
                    if k[0] == "cluster_scrape_staleness_seconds"}
        assert (("cluster_scrape_staleness_seconds",
                 (("instance", "a:1"),)) in keys)

    def test_merged_never_scraped_is_negative_staleness(self):
        fed = MetricsFederator(master=None)
        fed._scraped = {"gone:9": {"text": "", "ts": 0.0,
                                   "error": "boom"}}
        fed.merged()
        with metrics._lock:
            v = metrics._gauges.get(
                ("cluster_scrape_staleness_seconds",
                 (("instance", "gone:9"),)))
        assert v == -1
        assert fed.observability()["gone:9"]["Error"] == "boom"


# ---------------------------------------------------------------------
# pusher + master endpoints (in-process master)
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def master_srv():
    m = MasterServer(pulse_seconds=0.4, scrape_interval=3600.0)
    t = ServerThread(m.app).start()
    yield m, t
    t.stop()


class TestMasterEndpoints:
    def test_push_then_query(self, master_srv):
        m, t = master_srv
        tid = tracing.new_trace_id()
        root = _rec(trace_id=tid, service="s3", name="edge")
        child = _rec(trace_id=tid, parent_id=root["span_id"],
                     service="filer")
        r = requests.post(f"{t.url}/cluster/traces/push", json={
            "instance": "push:1", "service": "s3",
            "spans": [root, child], "dropped": 2}, timeout=5)
        assert r.status_code == 200 and r.json()["accepted"] == 2

        body = requests.get(f"{t.url}/cluster/traces", timeout=5).json()
        assert any(s["trace_id"] == tid for s in body["traces"])
        assert body["observability"]["Pushers"]["push:1"][
            "SpansDropped"] == 2

        tree = requests.get(f"{t.url}/cluster/traces",
                            params={"trace_id": tid}, timeout=5).json()
        assert tree["spans"] == 2
        assert tree["tree"][0]["children"][0]["service"] == "filer"

        otlp = requests.get(f"{t.url}/cluster/traces",
                            params={"format": "otlp",
                                    "trace_id": tid}, timeout=5).json()
        spans = [s for rs in otlp["resourceSpans"]
                 for ss in rs["scopeSpans"] for s in ss["spans"]]
        assert {s["traceId"] for s in spans} == {tid}

    def test_push_rejects_bad_bodies(self, master_srv):
        _, t = master_srv
        url = f"{t.url}/cluster/traces/push"
        assert requests.post(url, data=b"not json",
                             timeout=5).status_code == 400
        assert requests.post(url, json={"spans": "nope"},
                             timeout=5).status_code == 400
        assert requests.get(f"{t.url}/cluster/traces",
                            params={"trace_id": "f" * 32},
                            timeout=5).status_code == 404

    def test_cluster_status_observability_block(self, master_srv):
        _, t = master_srv
        obs = requests.get(f"{t.url}/cluster/status",
                           timeout=5).json()["Observability"]
        assert "TraceStoreTraces" in obs
        assert "Pushers" in obs and "Federation" in obs

    def test_cluster_metrics_merged(self, master_srv):
        _, t = master_srv
        body = requests.get(f"{t.url}/cluster/metrics", timeout=10).text
        # the master's own registry rides along, instance-labeled
        assert 'instance="master"' in body
        # one # TYPE line per family even with self + scrapes merged
        fams = [ln.split()[2] for ln in body.splitlines()
                if ln.startswith("# TYPE ")]
        assert len(fams) == len(set(fams))

    def test_master_own_spans_reach_collector(self, master_srv):
        m, t = master_srv
        # any traced master endpoint feeds the in-process sink
        requests.get(f"{t.url}/dir/status", timeout=5)
        deadline = time.time() + 5
        while time.time() < deadline:
            if any("master" in s["services"]
                   for s in m.collector.list_traces(limit=50)):
                break
            time.sleep(0.05)
        assert any("master" in s["services"]
                   for s in m.collector.list_traces(limit=50))


class TestSpanPusher:
    def test_end_to_end_push(self, master_srv, sample_config):
        m, t = master_srv
        tracing.configure(sample_rate=1.0)
        sp = SpanPusher(t.url, "unittest", "unit:1", interval=0.2)
        sp.start()
        try:
            pushed0 = _counter("trace_spans_pushed_total")
            with tracing.span("unit-root", service="unittest",
                              kind="server") as rec:
                pass
            tid = rec["trace_id"]
            # the master's in-process sink sees the span immediately;
            # wait for the HTTP push specifically; the pusher counts a
            # batch only once the master's answer is back, after the
            # master has listed it
            deadline = time.time() + 10
            while time.time() < deadline:
                if ("unit:1" in m.collector.observability()["Pushers"]
                        and _counter("trace_spans_pushed_total") > pushed0):
                    break
                time.sleep(0.05)
            assert m.collector.get_trace(tid) is not None
            assert _counter("trace_spans_pushed_total") > pushed0
            st = m.collector.observability()["Pushers"]["unit:1"]
            assert st["Service"] == "unittest"
            assert st["SpansDropped"] == 0
        finally:
            sp.stop()

    def test_queue_overflow_counts_drops_and_recovers(self, master_srv,
                                                      sample_config):
        m, t = master_srv
        tracing.configure(sample_rate=1.0)
        url = {"u": "http://127.0.0.1:1"}  # unreachable
        sp = SpanPusher(lambda: url["u"], "droptest", "drop:1",
                        batch_size=4, queue_max=4)
        dropped0 = _counter("trace_spans_dropped_total")
        for _ in range(10):
            sp._enqueue(_rec(service="droptest"))
        assert len(sp._q) == 4
        assert _counter("trace_spans_dropped_total") - dropped0 == 6
        assert sp.flush() is False  # master away: batch requeues
        assert len(sp._q) == 4
        url["u"] = t.url  # master is back
        assert sp.flush() is True
        assert len(sp._q) == 0
        st = m.collector.observability()["Pushers"]["drop:1"]
        assert st["SpansReceived"] == 4
        assert st["SpansDropped"] == 6  # loss is reported, not hidden

    def test_sampled_out_is_skipped_not_dropped(self, sample_config):
        tracing.configure(sample_rate=0.0)
        sp = SpanPusher("http://127.0.0.1:1", "s", "i")
        dropped0 = _counter("trace_spans_dropped_total")
        sp._enqueue(_rec())
        assert len(sp._q) == 0
        assert _counter("trace_spans_dropped_total") == dropped0

    def test_slow_span_tail_kept_despite_sampling(self, sample_config):
        """Keep-if-slow tail pass: with head sampling at 0, a span over
        -trace.slowThreshold is still enqueued and counted."""
        thresh = tracing.slow_threshold()
        tracing.configure(sample_rate=0.0, slow_threshold=0.5)
        try:
            sp = SpanPusher("http://127.0.0.1:1", "s", "i")
            kept0 = _counter("trace_push_tail_kept_total")
            sp._enqueue(_rec(duration=0.1))    # fast: sampled out
            assert len(sp._q) == 0
            sp._enqueue(_rec(duration=0.7))    # slow: tail-kept
            assert len(sp._q) == 1
            assert _counter("trace_push_tail_kept_total") == kept0 + 1
            # a disabled threshold (<= 0) disables the tail pass too
            tracing.configure(slow_threshold=0.0)
            sp._enqueue(_rec(duration=99.0))
            assert len(sp._q) == 1
        finally:
            tracing.configure(slow_threshold=thresh)

    def test_tail_keep_not_counted_when_head_sampled(self, sample_config):
        """A slow span whose trace IS head-sampled rides the normal
        path — the tail counter only counts rescues."""
        thresh = tracing.slow_threshold()
        tracing.configure(sample_rate=1.0, slow_threshold=0.5)
        try:
            sp = SpanPusher("http://127.0.0.1:1", "s", "i")
            kept0 = _counter("trace_push_tail_kept_total")
            sp._enqueue(_rec(duration=0.7))
            assert len(sp._q) == 1
            assert _counter("trace_push_tail_kept_total") == kept0
        finally:
            tracing.configure(slow_threshold=thresh)

    def test_stop_before_start_is_safe(self):
        SpanPusher("http://127.0.0.1:1", "s", "i").stop()


# ---------------------------------------------------------------------
# metrics pushgateway thread lifecycle (satellite fix)
# ---------------------------------------------------------------------


class TestMetricsPushThread:
    def test_stop_before_start_is_noop(self):
        metrics.stop_push()
        metrics.stop_push()

    def test_start_stop_start_cycle(self):
        metrics.start_push("127.0.0.1:1", "t", interval_seconds=3600)
        first = metrics._push_thread
        assert first is not None and first.is_alive()
        # idempotent while alive
        metrics.start_push("127.0.0.1:1", "t", interval_seconds=3600)
        assert metrics._push_thread is first
        metrics.stop_push()
        assert metrics._push_thread is None
        assert not first.is_alive()
        metrics.start_push("127.0.0.1:1", "t2", interval_seconds=3600)
        second = metrics._push_thread
        assert second is not None and second is not first
        metrics.stop_push()
        assert not second.is_alive()
