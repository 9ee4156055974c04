"""Span-tracing tests: nesting + contextvars propagation across scheduler
worker threads, trace-ring eviction, Chrome-export shape, the traces API,
and a /metrics round trip asserting the exposition output parses (including
escaped label values)."""

import http.client
import json
import re
import threading
import time

import pytest

from lighthouse_tpu import metrics, tracing
from lighthouse_tpu.scheduler import BeaconProcessor, W, WorkEvent


def _names(trace, with_depth=False):
    out = []

    def walk(sp, depth):
        out.append((depth, sp.name) if with_depth else sp.name)
        for c in sp.children:
            walk(c, depth + 1)

    walk(trace.root, 0)
    return out


class TestSpans:
    def test_nesting_fields_and_ring(self):
        with tracing.span("outer", slot=9) as root:
            with tracing.span("mid", kind="x"):
                with tracing.span("leaf"):
                    pass
            with tracing.span("mid2"):
                pass
        assert [c.name for c in root.children] == ["mid", "mid2"]
        assert root.children[0].children[0].name == "leaf"
        trace = tracing.TRACES.recent(root="outer", slot=9)[0]
        assert trace.root is root
        assert tracing.TRACES.get(trace.trace_id) is trace
        summary = tracing.trace_summary(trace)
        assert summary["slot"] == 9 and summary["root"] == "outer"
        assert summary["n_spans"] == 4

    def test_span_feeds_histogram(self):
        hist = metrics.histogram("test_tracing_stage_seconds", "test stage")
        before = hist.stats()[0]
        with tracing.span("hist_stage", hist=hist):
            pass
        assert hist.stats()[0] == before + 1

    def test_span_is_a_host_event_on_the_profilers_clock(self, tmp_path):
        """With the profiler annotation installed (as the device path's
        assembly does), a span around a jitted call is a host event of its
        name in the profiler's trace, enclosing the call's own event."""
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        @jax.jit
        def unit_profiled(x):
            return jnp.sin(x) * 2

        x = jnp.ones((64,))
        unit_profiled(x).block_until_ready()  # compile outside the window
        tracing.set_profiler_annotation(jax.profiler.TraceAnnotation)
        try:
            with jax.profiler.trace(str(tmp_path)):
                with tracing.span("unit_profiled_span"):
                    unit_profiled(x).block_until_ready()
        finally:
            tracing.set_profiler_annotation(None)
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                                "*.xplane.pb"))
        events = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
        (span,) = events["unit_profiled_span"]
        calls = events["PjitFunction(unit_profiled)"]
        assert calls and all(span[0] <= s and e <= span[1] for s, e in calls)

    def test_annotate_and_nested_dict(self):
        with tracing.span("outer") as sp:
            tracing.annotate(root="0xabcd")
        assert sp.fields["root"] == "0xabcd"
        trace = tracing.TRACES.recent(root="outer")[0]
        d = tracing.trace_to_dict(trace)
        assert d["root"]["fields"] and d["duration_s"] >= 0
        assert d["trace_id"] == trace.trace_id

    def test_per_trace_span_cap(self):
        with tracing.span("capped") as root:
            for _ in range(tracing.MAX_SPANS_PER_TRACE + 10):
                with tracing.span("child"):
                    pass
        trace = root.trace
        assert trace.n_spans == tracing.MAX_SPANS_PER_TRACE
        assert trace.dropped == 11  # root counts toward the cap
        assert len(root.children) == tracing.MAX_SPANS_PER_TRACE - 1


class TestRing:
    def test_eviction_is_per_root_and_bounded(self):
        ring = tracing.TraceRing(per_root=4)
        traces = []
        for i in range(6):
            t = tracing.Trace("busy", {"slot": i})
            t.root.close()
            ring.push(t)
            traces.append(t)
        rare = tracing.Trace("rare", {})
        rare.root.close()
        ring.push(rare)
        assert ring.get(traces[0].trace_id) is None  # evicted
        assert ring.get(traces[1].trace_id) is None
        assert ring.get(traces[-1].trace_id) is traces[-1]
        # a chatty root never evicts a different root's traces
        assert ring.get(rare.trace_id) is rare
        assert len(ring.recent(root="busy", limit=100)) == 4
        assert ring.recent(root="busy", slot=5)[0] is traces[5]


class TestCrossThread:
    def test_propagation_through_processor(self):
        p = BeaconProcessor(max_workers=1)
        try:
            seen = {}

            def work(_):
                seen["span"] = tracing.current_span()
                with tracing.span("inner_work"):
                    time.sleep(0.005)

            with tracing.span("request") as root:
                p.send(WorkEvent(work_type=W.GOSSIP_BLOCK, process=work))
                assert p.wait_idle(5.0)
            # the worker adopted the sender's trace...
            assert seen["span"].trace is root.trace
            names = _names(root.trace, with_depth=True)
            assert (1, "work:gossip_block") in names
            assert (2, "queue_wait") in names
            assert (2, "inner_work") in names
            # ...and the queue-wait seam fed the labeled histogram too
            n, total = metrics.QUEUE_WAIT_SECONDS.stats(work=W.GOSSIP_BLOCK)
            assert n >= 1 and total >= 0.0
        finally:
            p.shutdown()

    def test_worker_without_parent_starts_own_trace(self):
        p = BeaconProcessor(max_workers=1)
        try:
            p.send(WorkEvent(work_type=W.STATUS, process=lambda _: None))
            assert p.wait_idle(5.0)
            trace = tracing.TRACES.recent(root="work:status")[0]
            assert "queue_wait" in _names(trace)
        finally:
            p.shutdown()


class TestChromeExport:
    def test_event_shape(self):
        with tracing.span("chrome_root", slot=3):
            with tracing.span("stage"):
                time.sleep(0.002)
        trace = tracing.TRACES.recent(root="chrome_root")[0]
        out = tracing.trace_to_chrome(trace)
        assert out["displayTimeUnit"] == "ms"
        events = out["traceEvents"]
        assert len(events) == 2
        for ev in events:
            assert ev["ph"] == "X"
            assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(ev)
        stage = next(e for e in events if e["name"] == "stage")
        assert stage["dur"] >= 2000  # >= 2ms in microseconds
        assert json.loads(json.dumps(out))  # JSON-serializable end to end


# ---------------------------------------------------------------- exposition

# One sample line of the Prometheus text format: name{labels} value, where
# label values may contain escaped \" \\ \n sequences but no raw newline.
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
    r' [-+0-9.eE]+(e[-+]?[0-9]+)?$'
)


class TestExposition:
    def test_label_escaping(self):
        c = metrics.counter("test_tracing_escape_total", "escaping test")
        c.inc(path='a"b\\c\nd')
        line = next(
            l for l in metrics.render_prometheus().splitlines()
            if l.startswith("test_tracing_escape_total{")
        )
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        assert _SAMPLE_RE.match(line), line

    def test_full_render_parses(self):
        h = metrics.histogram("test_tracing_parse_seconds", "parse test")
        h.observe(0.5, stage='we"ird\\')
        for line in metrics.render_prometheus().splitlines():
            if line.startswith("#"):
                assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*", line), line
            elif line:
                assert _SAMPLE_RE.match(line), line

    def test_process_metrics_exported(self):
        out = metrics.render_prometheus()
        for name in ("process_cpu_seconds_total",
                     "process_resident_memory_bytes",
                     "process_start_time_seconds"):
            assert f"# TYPE {name}" in out
        start = float(next(
            l for l in out.splitlines()
            if l.startswith("process_start_time_seconds ")
        ).split()[1])
        assert 0 < start <= time.time() + 1

    def test_reads_locked_consistently(self):
        # stats()/get() take the series lock like the writers — hammer one
        # histogram from two threads while reading; totals must be sane.
        h = metrics.histogram("test_tracing_lock_seconds", "lock test")
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                h.observe(0.001)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            for _ in range(200):
                n, total = h.stats()
                assert total >= 0.0 and n >= 0
        finally:
            stop.set()
            t.join(timeout=2)


# ----------------------------------------------------------------- HTTP API


@pytest.fixture(scope="module")
def traced_chain():
    from lighthouse_tpu.chain import BeaconChainHarness
    from lighthouse_tpu.crypto.bls.backends import set_backend
    from lighthouse_tpu.http_api import HttpApiServer

    set_backend("fake")
    harness = BeaconChainHarness(validator_count=16, fake_crypto=True)
    processor = BeaconProcessor(max_workers=2)
    server = HttpApiServer(harness.chain, processor=processor).start()
    yield harness, processor, server
    server.stop()
    processor.shutdown()
    set_backend("host")


def _get_json(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_block_import_trace_via_scheduler_and_api(traced_chain):
    """ISSUE 2 acceptance: a block imported through the scheduler yields a
    retrievable trace whose tree has queue-wait, state-transition,
    device-batch, fork-choice, and store-write spans, and the same stages
    appear in the /metrics histograms."""
    harness, processor, server = traced_chain
    harness.advance_slot()
    signed = harness.produce_signed_block()
    st_before = metrics.BLOCK_STATE_TRANSITION_SECONDS.stats()[0]
    processor.send(WorkEvent(
        work_type=W.GOSSIP_BLOCK,
        process=lambda _: harness.chain.process_block(
            signed, block_delay_seconds=1.0),
    ))
    assert processor.wait_idle(15.0)

    status, listing = _get_json(
        server.port, f"/lighthouse/traces?root=work:gossip_block&slot={int(signed.message.slot)}"
    )
    assert status == 200 and listing["data"]
    trace_id = listing["data"][0]["trace_id"]

    status, tree = _get_json(server.port, f"/lighthouse/traces/{trace_id}")
    assert status == 200
    names = set()

    def walk(sp):
        names.add(sp["name"])
        for c in sp["children"]:
            walk(c)

    walk(tree["data"]["root"])
    assert {"queue_wait", "block_import", "state_transition", "device_batch",
            "fork_choice", "store_write", "head_recompute"} <= names

    status, chrome = _get_json(
        server.port, f"/lighthouse/traces/{trace_id}?format=chrome")
    assert status == 200
    assert any(e["name"] == "block_import" for e in chrome["traceEvents"])

    # the SAME instrumentation points fed the aggregate histograms
    assert metrics.BLOCK_STATE_TRANSITION_SECONDS.stats()[0] > st_before
    assert metrics.BLOCK_ARRIVAL_DELAY_SECONDS.stats()[0] >= 1
    assert metrics.BLOCK_IMPORTED_DELAY_SECONDS.stats()[0] >= 1

    status, missing = _get_json(server.port, "/lighthouse/traces/nope")
    assert status == 404


def test_http_requests_labeled_by_route_template(traced_chain):
    harness, processor, server = traced_chain
    _get_json(server.port, "/eth/v1/node/version")
    _get_json(server.port, "/eth/v1/beacon/states/head/root")
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    # the TEMPLATE, not the raw path, is the label (bounded cardinality)
    assert 'route="/eth/v1/beacon/states/{state_id}/root"' in text
    assert 'route="/eth/v1/node/version"' in text
    assert 'route="/eth/v1/beacon/states/head/root"' not in text
    assert metrics.HTTP_REQUESTS.get(
        method="GET", route="/eth/v1/node/version") >= 1
    # routed GETs produce per-route request traces — each route template is
    # its own bounded sub-ring, so chatty polling can't evict rare traces
    assert tracing.TRACES.recent(root="http:GET /eth/v1/node/version")
