"""Device-telemetry tests (ISSUE 4): compile-cache counting (once per
shape, flat on re-invocation), occupancy math against hand-computed
batches, flight-recorder ring bounds + trace-id linkage, the
``/lighthouse/device*`` endpoint shapes, the profiler 501 path on CPU,
and the SSE sent/dropped satellite."""

import http.client
import json
import queue
import random

import numpy as np
import pytest

from lighthouse_tpu import device_telemetry, metrics, tracing
from lighthouse_tpu.crypto.bls import api

rng = random.Random(0xD37)


def make_set(msg: bytes, n_keys: int = 1):
    sks = [api.SecretKey.random() for _ in range(n_keys)]
    pks = [sk.public_key() for sk in sks]
    agg = api.AggregateSignature.infinity()
    for sk in sks:
        agg.add_assign(sk.sign(msg))
    return api.SignatureSet.multiple_pubkeys(agg, pks, msg)


# --------------------------------------------------------------- unit layer


class TestCompileCache:
    def test_counter_fires_once_per_shape_then_stays_flat(self):
        cache = device_telemetry.CompileCache()
        before = metrics.DEVICE_PROGRAM_COMPILES.get(op="test_cc", shape="4x2")
        assert cache.note_dispatch("test_cc", (4, 2), 1.5) is True
        assert cache.note_dispatch("test_cc", (4, 2), 0.001) is False
        assert cache.note_dispatch("test_cc", (4, 2), 0.001) is False
        assert metrics.DEVICE_PROGRAM_COMPILES.get(
            op="test_cc", shape="4x2") == before + 1
        # a different shape of the same op is its own program
        assert cache.note_dispatch("test_cc", (8, 2), 0.7) is True
        inv = {e["shape"]: e for e in cache.inventory()}
        assert inv["4x2"]["invocations"] == 3
        assert inv["4x2"]["compile_seconds"] == 1.5
        assert inv["8x2"]["invocations"] == 1

    def test_compile_seconds_histogram_fed_on_first_dispatch_only(self):
        cache = device_telemetry.CompileCache()
        n0 = metrics.DEVICE_PROGRAM_COMPILE_SECONDS.stats(op="test_hist")[0]
        cache.note_dispatch("test_hist", (1,), 2.0)
        cache.note_dispatch("test_hist", (1,), 2.0)
        n1, total = metrics.DEVICE_PROGRAM_COMPILE_SECONDS.stats(op="test_hist")
        assert n1 == n0 + 1 and total >= 2.0


class TestOccupancy:
    def test_hand_computed_batch(self):
        rec = device_telemetry.FlightRecorder(capacity=8)
        old_ring = device_telemetry.FLIGHT_RECORDER
        device_telemetry.FLIGHT_RECORDER = rec
        try:
            sets0 = metrics.DEVICE_BATCH_WASTED_LANES.get(op="test_occ", axis="sets")
            keys0 = metrics.DEVICE_BATCH_WASTED_LANES.get(op="test_occ", axis="keys")
            entry = device_telemetry.record_batch(
                op="test_occ", shape=(8, 4), n_live=5, live_keys=13,
            )
            # 5 live sets in an 8-bucket; 13 live keys across 8*4 lanes
            assert entry["occupancy_sets"] == pytest.approx(5 / 8)
            assert entry["occupancy_keys"] == pytest.approx(13 / 32, abs=1e-4)
            assert metrics.DEVICE_BATCH_WASTED_LANES.get(
                op="test_occ", axis="sets") == sets0 + 3
            assert metrics.DEVICE_BATCH_WASTED_LANES.get(
                op="test_occ", axis="keys") == keys0 + 19
        finally:
            device_telemetry.FLIGHT_RECORDER = old_ring

    def test_full_batch_is_unit_occupancy(self):
        entry = device_telemetry.record_batch(
            op="test_occ_full", shape=(4, 2), n_live=4, live_keys=8)
        assert entry["occupancy_sets"] == 1.0
        assert entry["occupancy_keys"] == 1.0


class TestFlightRecorder:
    def test_ring_is_bounded_and_newest_first(self):
        ring = device_telemetry.FlightRecorder(capacity=4)
        for i in range(6):
            ring.record({"op": "x", "i": i})
        assert len(ring) == 4
        assert ring.recorded_total == 6
        recent = ring.recent(limit=10)
        assert [r["i"] for r in recent] == [5, 4, 3, 2]
        assert [r["seq"] for r in recent] == [6, 5, 4, 3]

    def test_ring_size_env_configurable(self):
        """ISSUE 17 satellite: ``LIGHTHOUSE_TPU_FLIGHT_RING`` sizes the
        ring (long soaks grow it so pre-incident records survive to the
        postmortem bundle), with the legacy capacity name as fallback.
        The constant is read at import, so the probe runs in a child."""
        import os
        import subprocess
        import sys

        probe = (
            "from lighthouse_tpu import device_telemetry as dt\n"
            "assert dt.FLIGHT_RECORDER_CAPACITY == 32\n"
            "assert dt.FLIGHT_RECORDER.capacity == 32\n"
            "for i in range(100):\n"
            "    dt.record_batch(op='ring_probe', shape=(4,), n_live=2)\n"
            "assert len(dt.FLIGHT_RECORDER) == 32\n"
            "assert dt.FLIGHT_RECORDER.recorded_total == 100\n"
            "print('RING_OK')\n"
        )
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run(
            [sys.executable, "-c", probe], capture_output=True, text=True,
            cwd=repo_root, timeout=120,
            env={**os.environ, "LIGHTHOUSE_TPU_FLIGHT_RING": "32"})
        assert res.returncode == 0, res.stderr
        assert "RING_OK" in res.stdout
        # the legacy env name still works when the new one is absent
        env = {k: v for k, v in os.environ.items()
               if k != "LIGHTHOUSE_TPU_FLIGHT_RING"}
        env["LIGHTHOUSE_TPU_FLIGHT_RECORDER_CAPACITY"] = "16"
        res = subprocess.run(
            [sys.executable, "-c",
             "from lighthouse_tpu import device_telemetry as dt\n"
             "assert dt.FLIGHT_RECORDER.capacity == 16\n"],
            capture_output=True, text=True, cwd=repo_root, timeout=120,
            env=env)
        assert res.returncode == 0, res.stderr

    def test_filters(self):
        ring = device_telemetry.FlightRecorder(capacity=8)
        ring.record({"op": "a", "trace_id": "t1"})
        ring.record({"op": "b", "trace_id": "t2"})
        ring.record({"op": "a", "trace_id": "t2"})
        assert [r["op"] for r in ring.recent(op="a")] == ["a", "a"]
        assert [r["op"] for r in ring.recent(trace_id="t2")] == ["a", "b"]

    def test_summary_percentiles(self):
        device_telemetry.reset_for_tests()
        for live in (2, 4, 8):
            device_telemetry.record_batch(op="test_pct", shape=(8,), n_live=live)
        s = device_telemetry.summary()
        occ = s["occupancy"]["test_pct"]["sets"]
        assert occ["n"] == 3
        assert occ["min"] == pytest.approx(0.25)
        assert occ["max"] == pytest.approx(1.0)
        assert s["flight_recorder"]["stored"] == 3
        assert isinstance(s["memory"], list)  # cpu devices listed, no stats

    def test_summary_percentiles_grouped_per_op(self):
        """An unpadded op at occupancy 1.0 must not dilute the padding-waste
        percentiles of a bucketed op."""
        device_telemetry.reset_for_tests()
        for _ in range(10):
            device_telemetry.record_batch(op="test_unpadded", shape=(4,), n_live=4)
        device_telemetry.record_batch(op="test_padded", shape=(8,), n_live=4)
        occ = device_telemetry.summary()["occupancy"]
        assert occ["test_unpadded"]["sets"]["p50"] == pytest.approx(1.0)
        assert occ["test_padded"]["sets"]["p50"] == pytest.approx(0.5)


# ---------------------------------------------------- device verify (real)


class TestVerifyIntegration:
    def test_compile_counted_once_per_bucket_shape(self):
        """Acceptance: a fresh bucket shape increments
        device_program_compiles_total exactly once; repeat calls do not."""
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        device_telemetry.reset_for_tests()
        before = metrics.DEVICE_PROGRAM_COMPILES.get(op="bls_verify", shape="1x1")
        s = make_set(b"telemetry-1")
        assert verify_signature_sets_device([s], seed=b"t") is True
        assert metrics.DEVICE_PROGRAM_COMPILES.get(
            op="bls_verify", shape="1x1") == before + 1
        assert verify_signature_sets_device([s], seed=b"t") is True
        assert metrics.DEVICE_PROGRAM_COMPILES.get(
            op="bls_verify", shape="1x1") == before + 1  # flat on re-invoke

        records = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")
        assert len(records) >= 2
        newest, second = records[0], records[1]
        assert second["compiled"] is True and newest["compiled"] is False
        assert newest["verdict"] is True and newest["host_fallback"] is False
        assert newest["shape"] == "1x1" and newest["n_live"] == 1
        assert newest["occupancy_sets"] == 1.0 and newest["occupancy_keys"] == 1.0
        assert {"setup", "dispatch", "wait", "verdict"} <= set(newest["stages_s"])

    def test_trace_id_links_flight_record_to_trace_tree(self):
        """Acceptance: the flight-recorder entry carries the same trace id
        as the enclosing trace, and the device_verify span carries the
        record's seq (cross-reference in both directions)."""
        from lighthouse_tpu.crypto.bls.backends import jax_backend

        s = make_set(b"telemetry-linkage")
        with tracing.span("block_import", slot=77) as root:
            assert jax_backend.verify_signature_sets([s], seed=b"t") is True
        trace_id = root.trace.trace_id
        records = device_telemetry.FLIGHT_RECORDER.recent(trace_id=trace_id)
        assert len(records) == 1
        dv = next(c for c in root.children if c.name == "device_verify")
        assert dv.fields["flight_seq"] == records[0]["seq"]
        # and the trace is retrievable from the ring by that id
        assert tracing.TRACES.get(trace_id) is root.trace

    def test_w_at_infinity_host_fallback_is_counted_and_stamped(self, monkeypatch):
        from lighthouse_tpu.ops import verify as verify_mod

        # Force the W-at-infinity path: zero Z limbs out of the "device".
        fake_w_z = np.zeros((2, 25), np.int32)
        monkeypatch.setattr(
            verify_mod.PROGRAM, "fn",
            lambda *batch: (np.zeros((12, 25), np.int32), fake_w_z),
        )
        before = metrics.DEVICE_HOST_FALLBACK.get(reason="w_at_infinity")
        s = make_set(b"fallback")
        with tracing.span("fallback_root") as root:
            assert verify_mod.verify_signature_sets_device([s], seed=b"t") is True
        assert metrics.DEVICE_HOST_FALLBACK.get(
            reason="w_at_infinity") == before + 1
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")[0]
        assert rec["host_fallback"] is True
        assert rec["fallback_reason"] == "w_at_infinity"
        assert rec["verdict"] is True  # the host re-verify decided
        assert device_telemetry.host_fallback_counts()["w_at_infinity"] >= 1
        # stamped on the trace: the verdict span carries the fallback flag
        verdicts = [sp for sp in _walk(root) if sp.name == "device_batch_verdict"]
        assert any(sp.fields.get("host_fallback") for sp in verdicts)
        assert root.fields.get("host_fallback") is True


def _walk(sp):
    yield sp
    for c in sp.children:
        yield from _walk(c)


# ------------------------------------------------------------------ HTTP API


@pytest.fixture(scope="module")
def device_api():
    from lighthouse_tpu.chain import BeaconChainHarness
    from lighthouse_tpu.crypto.bls.backends import set_backend
    from lighthouse_tpu.http_api import HttpApiServer

    set_backend("fake")
    harness = BeaconChainHarness(validator_count=8, fake_crypto=True)
    server = HttpApiServer(harness.chain).start()
    yield harness, server
    server.stop()
    set_backend("host")


def _request(port, method, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request(method, path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class TestEndpoints:
    def test_device_summary_shape(self, device_api):
        device_telemetry.reset_for_tests()
        device_telemetry.note_dispatch("bls_verify", (8, 4), 1.25)
        device_telemetry.record_batch(
            op="bls_verify", shape=(8, 4), n_live=6, live_keys=20,
            trace_id="abc", compiled=True)
        _, server = device_api
        status, out = _request(server.port, "GET", "/lighthouse/device")
        assert status == 200
        data = out["data"]
        assert {"programs", "occupancy", "host_fallbacks",
                "flight_recorder", "memory"} <= set(data)
        prog = next(p for p in data["programs"] if p["shape"] == "8x4")
        assert prog["op"] == "bls_verify"
        assert prog["compile_seconds"] == 1.25
        assert data["occupancy"]["bls_verify"]["sets"]["max"] == pytest.approx(0.75)
        assert data["flight_recorder"]["capacity"] >= 1
        # cpu run: devices are listed, memory stats simply absent
        for dev in data["memory"]:
            assert {"id", "platform"} <= set(dev)

    def test_device_batches_listing_and_filters(self, device_api):
        _, server = device_api
        status, out = _request(
            server.port, "GET", "/lighthouse/device/batches?op=bls_verify&limit=5")
        assert status == 200
        assert out["data"], "flight recorder should have records"
        for rec in out["data"]:
            assert rec["op"] == "bls_verify"
            assert {"seq", "t_ms", "shape", "n_live"} <= set(rec)
        status, filtered = _request(
            server.port, "GET", "/lighthouse/device/batches?trace_id=abc")
        assert status == 200
        assert all(r["trace_id"] == "abc" for r in filtered["data"])
        status, _ = _request(
            server.port, "GET", "/lighthouse/device/batches?limit=junk")
        assert status == 400

    def test_profiler_501_on_cpu(self, device_api, monkeypatch):
        monkeypatch.delenv("LIGHTHOUSE_TPU_FORCE_PROFILER", raising=False)
        _, server = device_api
        status, out = _request(
            server.port, "POST", "/lighthouse/device/profile?seconds=1")
        assert status == 501
        assert "cpu" in out["message"]

    def test_profiler_bad_seconds(self, device_api):
        _, server = device_api
        status, _ = _request(
            server.port, "POST", "/lighthouse/device/profile?seconds=zero")
        assert status == 400
        status, _ = _request(
            server.port, "POST", "/lighthouse/device/profile?seconds=-3")
        assert status == 400

    def test_events_subscribers_summary(self, device_api):
        harness, server = device_api
        sub = harness.chain.events.subscribe(["head"])
        try:
            harness.chain.events.publish("head", {"slot": "1"})
            status, out = _request(
                server.port, "GET", "/lighthouse/events/subscribers")
            assert status == 200
            entry = next(e for e in out["data"] if e["topics"] == ["head"])
            assert entry["queue_depth"] == 1
            assert entry["dropped"] == 0
            assert {"sent", "queue_capacity", "dropped_by_topic"} <= set(entry)
        finally:
            harness.chain.events.unsubscribe(sub)


# ------------------------------------------------------------- SSE satellite


class TestSseDropAccounting:
    def test_publish_counts_drops_per_topic(self):
        from lighthouse_tpu.chain import events as ev

        bus = ev.EventBus()
        sub = bus.subscribe([ev.TOPIC_HEAD, ev.TOPIC_BLOCK])
        sub.q = queue.Queue(maxsize=1)  # shrink to force drops
        before = metrics.SSE_EVENTS_DROPPED.get(topic=ev.TOPIC_HEAD)
        bus.publish(ev.TOPIC_HEAD, {"slot": "1"})   # fills the queue
        bus.publish(ev.TOPIC_HEAD, {"slot": "2"})   # dropped
        bus.publish(ev.TOPIC_BLOCK, {"slot": "2"})  # dropped (shared queue)
        assert sub.dropped == 2
        assert sub.dropped_by_topic == {ev.TOPIC_HEAD: 1, ev.TOPIC_BLOCK: 1}
        assert metrics.SSE_EVENTS_DROPPED.get(topic=ev.TOPIC_HEAD) == before + 1
        summary = bus.summary()
        assert summary[0]["dropped"] == 2
        assert summary[0]["queue_depth"] == 1

    def test_sse_stream_counts_sent(self):
        """End to end: events written to a live /eth/v1/events stream bump
        http_sse_events_sent_total{topic} and the subscriber's sent
        figure."""
        from lighthouse_tpu.chain import BeaconChainHarness
        from lighthouse_tpu.crypto.bls.backends import set_backend
        from lighthouse_tpu.http_api import HttpApiServer

        set_backend("fake")
        harness = BeaconChainHarness(validator_count=8, fake_crypto=True)
        server = HttpApiServer(harness.chain).start()
        try:
            import time as _t

            before = metrics.SSE_EVENTS_SENT.get(topic="head")
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=10)
            conn.request("GET", "/eth/v1/events?topics=head")
            resp = conn.getresponse()  # returns once headers are out, i.e.
            # after _serve_events subscribed — publishing now is safe
            harness.chain.events.publish("head", {"slot": "3", "block": "0x00"})
            buf = b""
            deadline = _t.time() + 5
            while b"\n\n" not in buf and _t.time() < deadline:
                chunk = resp.read1(4096)
                if not chunk:
                    break
                buf += chunk
            conn.close()
            assert b"event: head" in buf
            # the writer counted the delivery
            deadline = _t.time() + 3
            while (metrics.SSE_EVENTS_SENT.get(topic="head") == before
                   and _t.time() < deadline):
                _t.sleep(0.05)
            assert metrics.SSE_EVENTS_SENT.get(topic="head") == before + 1
        finally:
            server.stop()
            set_backend("host")


class TestProgramStages:
    def test_two_scope_program_maps_every_instruction_to_its_scope(self):
        """Each instruction of a compiled two-scope program is mapped to its
        scope: compute by its own metadata, a loop's body and condition by
        the loop, an unlabelled fusion by its operands."""
        import re

        import jax
        import jax.numpy as jnp

        @jax.jit
        def two_scopes(x, y):
            with jax.named_scope("st_first"):
                a = jnp.sin(x) @ y
                a = jax.lax.fori_loop(0, 40, lambda i, c: jnp.cos(c) * 2 + c, a)
            with jax.named_scope("st_second"):
                return jnp.tanh(a).sum(axis=0) * 3

        x = jnp.ones((32, 32))
        text = two_scopes.lower(x, x).compile().as_text()
        stages = device_telemetry.hlo_stage_map(text, ("st_first", "st_second"))
        assert set(stages.values()) == {"st_first", "st_second"}
        entry = text[text.index("ENTRY"):]
        ops = re.findall(r"^\s*(?:ROOT\s+)?%(\S+) = .*?\s([a-z][\w-]*)\(",
                         entry, re.M)
        compute = [(n, op) for n, op in ops if op not in (
            "parameter", "constant", "tuple", "get-tuple-element", "copy")]
        assert compute and all(n in stages for n, _ in compute), compute
        by_op = {op: stages[n] for n, op in compute}
        assert by_op["while"] == "st_first" and by_op["dot"] == "st_first"
        # every instruction named in the map is one of the program's
        names = set(re.findall(r"^\s*(?:ROOT\s+)?%(\S+) = ", text, re.M))
        assert set(stages) <= names

    def test_program_stages_reads_the_dispatched_executable(self):
        """The map of a dispatched bucket comes from jit's caches (no new
        backend compile), is cached, and names the verifier's stages."""
        import jax

        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        compiles = []

        def hear(event, duration, **kw):
            if event.endswith("backend_compile_duration"):
                compiles.append(event)

        s = make_set(b"stage-map", n_keys=2)
        assert verify_signature_sets_device([s, s], seed=b"t") is True
        jax.monitoring.register_event_duration_secs_listener(hear)
        try:
            stages = device_telemetry.program_stages("bls_verify", (2, 2))
        finally:
            jax.monitoring.unregister_event_duration_listener(hear)
        assert compiles == []
        assert {"s1_key_sum", "s2_g1_ladder", "s3_g2_msm", "h2c_clear",
                "miller_loop", "final_exp"} == set(stages.values())
        assert device_telemetry.program_stages("bls_verify", (2, 2)) is stages
        assert device_telemetry.program_stages("epoch_deltas", (8,)) is None
