"""Device-supervisor chaos matrix (ISSUE 5): breaker state machine units,
watchdog timeout (hang injection), injected compile failure, transient
error with a successful split-batch retry, fail-first-N breaker trip with
HALF_OPEN recovery, scheduler re-enqueue on an escaped dispatch deadline,
host-fallback parity for the sha/epoch ops, and the acceptance scenario:
an end-to-end chain-harness import with every ``device.dispatch`` faulted
that still reaches the correct head via the host path — with the breaker
OPEN→recovered visible on ``GET /lighthouse/device`` and as SSE events."""

import http.client
import json
import random
import threading
import time

import numpy as np
import pytest

from lighthouse_tpu import device_supervisor as ds
from lighthouse_tpu import device_telemetry
from lighthouse_tpu import fault_injection as fi
from lighthouse_tpu import metrics
from lighthouse_tpu.crypto.bls import api

rng = random.Random(0x5123)


@pytest.fixture(autouse=True)
def _clean_state():
    fi.reset_for_tests()
    ds.reset_for_tests()
    yield
    fi.reset_for_tests()
    ds.reset_for_tests()


def make_set(msg: bytes, n_keys: int = 1):
    sks = [api.SecretKey.random() for _ in range(n_keys)]
    pks = [sk.public_key() for sk in sks]
    agg = api.AggregateSignature.infinity()
    for sk in sks:
        agg.add_assign(sk.sign(msg))
    return api.SignatureSet.multiple_pubkeys(agg, pks, msg)


# ------------------------------------------------------- breaker state unit


class TestCircuitBreaker:
    def cfg(self, **kw):
        defaults = dict(failure_threshold=2, open_cooldown_s=0.15,
                        probe_successes=2)
        defaults.update(kw)
        return ds.BreakerConfig(**defaults)

    def test_trips_after_consecutive_failures_only(self):
        br = ds.CircuitBreaker("t1", self.cfg())
        assert br.record_failure("device_error") == []
        assert br.record_success() == []  # resets the streak
        assert br.record_failure("device_error") == []
        transitions = br.record_failure("device_error")
        assert [(a, b) for a, b, _ in transitions] == [("closed", "open")]
        assert br.state == "open"
        assert br.trips_total == 1

    def test_open_routes_host_until_cooldown_then_probes(self):
        br = ds.CircuitBreaker("t2", self.cfg(probe_successes=2))
        br.record_failure("x")
        br.record_failure("x")
        route, _ = br.route()
        assert route == "host"
        time.sleep(0.2)
        route, transitions = br.route()
        assert route == "device"
        assert [(a, b) for a, b, _ in transitions] == [("open", "half_open")]
        # one probe success is not enough at probe_successes=2
        assert br.record_success() == []
        assert br.state == "half_open"
        br.route()
        transitions = br.record_success()
        assert [(a, b) for a, b, _ in transitions] == [("half_open", "closed")]
        assert br.probes_total == 2

    def test_probe_failure_reopens(self):
        br = ds.CircuitBreaker("t3", self.cfg())
        br.record_failure("x")
        br.record_failure("x")
        time.sleep(0.2)
        route, _ = br.route()
        assert route == "device"
        transitions = br.record_failure("still_down")
        assert [(a, b) for a, b, _ in transitions] == [("half_open", "open")]
        assert transitions[0][2] == "probe_failed:still_down"
        assert br.trips_total == 2

    def test_transitions_publish_sse_and_metrics(self):
        from lighthouse_tpu.chain import events as ev

        bus = ev.EventBus()
        ds.register_event_bus(bus)
        sub = bus.subscribe([ev.TOPIC_DEVICE_BREAKER])
        ds.SUPERVISOR.configure(config=ds.BreakerConfig(
            failure_threshold=1, open_cooldown_s=0.05, probe_successes=1))
        before = metrics.DEVICE_BREAKER_TRANSITIONS.get(op="t_sse", to="open")

        def boom():
            raise RuntimeError("injected")

        assert ds.run("t_sse", boom, host_fn=lambda: "host") == "host"
        assert metrics.DEVICE_BREAKER_TRANSITIONS.get(
            op="t_sse", to="open") == before + 1
        assert metrics.DEVICE_BREAKER_STATE.get(op="t_sse") == 1
        topic, data = sub.q.get_nowait()
        assert topic == ev.TOPIC_DEVICE_BREAKER
        assert (data["op"], data["from"], data["to"]) == ("t_sse", "closed", "open")
        assert "timestamp_ms" in data
        # recovery emits half_open then closed
        time.sleep(0.1)
        assert ds.run("t_sse", lambda: "dev", host_fn=lambda: "host") == "dev"
        states = [sub.q.get_nowait()[1]["to"] for _ in range(2)]
        assert states == ["half_open", "closed"]
        assert metrics.DEVICE_BREAKER_STATE.get(op="t_sse") == 0


# ------------------------------------------- programming errors propagate


class TestProgrammingErrors:
    """The breaker absorbs device and runtime faults only: a bug in the
    device op (here raised inside ``device_fn``) reaches the caller, never
    the host, and leaves the breaker closed."""

    @pytest.mark.parametrize("exc", [ImportError, TypeError])
    def test_propagates_without_fallback(self, exc):
        def device_fn():
            raise exc("bug in the device op")

        before = sum(metrics.DEVICE_HOST_FALLBACK.snapshot().values())
        for _ in range(4):  # more than the breaker's failure threshold
            with pytest.raises(exc, match="bug in the device op"):
                ds.run("prog_error_probe", device_fn, host_fn=lambda: True,
                       split_fn=lambda: [device_fn, device_fn])
        assert ds.breaker_state("prog_error_probe") == "closed"
        assert sum(metrics.DEVICE_HOST_FALLBACK.snapshot().values()) == before

    def test_injected_device_fault_still_falls_back(self):
        def device_fn():
            fi.check("device.dispatch", op="prog_error_probe")
            return False  # never reached: the fault fires first

        fi.install("device.dispatch", "error", op="prog_error_probe")
        before = metrics.DEVICE_HOST_FALLBACK.get(reason="device_error")
        info = {}
        assert ds.run("prog_error_probe", device_fn, host_fn=lambda: True,
                      info=info) is True
        assert info["route"] == "host"
        assert metrics.DEVICE_HOST_FALLBACK.get(
            reason="device_error") == before + 1


# -------------------------------------------------------- supervised verify


def _fallbacks(reason):
    return metrics.DEVICE_HOST_FALLBACK.get(reason=reason)


class TestSupervisedBlsVerify:
    def test_injected_compile_error_falls_back_to_host(self):
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        device_telemetry.reset_for_tests()  # (1,1) becomes "unseen" again
        fi.install("device.compile", "error", op="bls_verify")
        before = _fallbacks("device_error")
        s = make_set(b"compile-fault")
        assert verify_signature_sets_device([s], seed=b"t") is True
        assert _fallbacks("device_error") == before + 1
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")[0]
        assert rec["host_fallback"] is True
        assert rec["fallback_reason"] == "device_error"
        assert rec["verdict"] is True
        assert rec["breaker_state"] == "closed"  # 1 failure < threshold

    def test_transient_error_split_retry_succeeds(self):
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        fi.install("device.dispatch", "error", op="bls_verify", first_n=1)
        ok_before = metrics.DEVICE_SPLIT_RETRIES.get(
            op="bls_verify", outcome="success")
        fb_before = metrics.DEVICE_HOST_FALLBACK.get(reason="device_error")
        sets = [make_set(b"split-a"), make_set(b"split-b")]
        assert verify_signature_sets_device(sets, seed=b"t") is True
        assert metrics.DEVICE_SPLIT_RETRIES.get(
            op="bls_verify", outcome="success") == ok_before + 1
        # no host fallback: the halves decided on the device
        assert metrics.DEVICE_HOST_FALLBACK.get(
            reason="device_error") == fb_before
        assert ds.SUPERVISOR.breaker("bls_verify").state == "closed"

    def test_batch_global_ops_never_split(self):
        """epoch_deltas[_leak] compute registry-wide sums: halves are not
        independent, so the supervisor must refuse split-retry for them even
        if a caller wires a split_fn — with 4096-scale standard buckets a
        mis-split would silently change the op's semantics.  A device error
        goes straight to the host fallback instead."""
        split_calls = []
        for op in sorted(ds.NO_SPLIT_OPS):
            fb_before = metrics.DEVICE_HOST_FALLBACK.get(reason="device_error")

            def bad_device():
                raise RuntimeError("injected")

            def spy_split():
                split_calls.append(op)
                return [lambda: 1, lambda: 2]

            out = ds.run(op, bad_device, host_fn=lambda: "host-exact",
                         split_fn=spy_split, combine_fn=sum)
            assert out == "host-exact"
            assert split_calls == []
            assert metrics.DEVICE_HOST_FALLBACK.get(
                reason="device_error") == fb_before + 1
        # bls_verify is NOT in the registry: its split path stays available
        assert "bls_verify" not in ds.NO_SPLIT_OPS

    def test_top_bucket_split_halves_at_smaller_bucket(self):
        """A transient error on a top-bucket-shaped bls batch retries as two
        halves at the half bucket (the split path stays shape-bucketed) —
        asserted structurally on the verify split_fn contract: each half is
        its own supervised dispatch at its own bucket."""
        from lighthouse_tpu.ops import verify as v

        assert v.MAX_SETS_PER_DISPATCH == v.N_BUCKETS[-1] == 4096
        # _bucket pads a split half of 2048 into the 2048 bucket, not 4096
        assert v._bucket(2048, v.N_BUCKETS) == 2048
        assert v._bucket(2049, v.N_BUCKETS) == 4096

    def test_split_retry_detects_bad_half(self):
        """A batch with one invalid set still verifies False through the
        split path (halves AND together)."""
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        good = make_set(b"good")
        sk = api.SecretKey.random()
        bad = api.SignatureSet.single_pubkey(
            sk.sign(b"other message"), sk.public_key(), b"bad")
        fi.install("device.dispatch", "error", op="bls_verify", first_n=1)
        assert verify_signature_sets_device([good, bad], seed=b"t") is False

    def test_hang_trips_watchdog_and_host_decides(self):
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        ds.SUPERVISOR.configure(deadlines={"bls_verify": 0.3})
        fi.install("device.dispatch", "hang", op="bls_verify",
                   sleep_s=1.5, first_n=1)
        to_before = metrics.DEVICE_DISPATCH_TIMEOUTS.get(op="bls_verify")
        fb_before = _fallbacks("dispatch_timeout")
        s = make_set(b"hang-fault")
        assert verify_signature_sets_device([s], seed=b"t") is True
        stranded = [t for t in threading.enumerate()
                    if t.name == "device-dispatch-bls_verify"]
        # the caller resolved through the host without waiting out the hang
        assert metrics.DEVICE_DISPATCH_TIMEOUTS.get(
            op="bls_verify") == to_before + 1
        assert _fallbacks("dispatch_timeout") == fb_before + 1
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")[0]
        assert rec["fallback_reason"] == "dispatch_timeout"
        # a fresh worker serves the next batch on the device
        assert verify_signature_sets_device([s], seed=b"t") is True
        assert ds.SUPERVISOR.breaker("bls_verify").state == "closed"
        # let the abandoned worker finish its hang and the batch it then
        # dispatches: still running inside the next test, its verdict would
        # fire that test's fault plans
        for t in stranded:
            t.join(timeout=60)
            assert not t.is_alive()

    def test_split_half_disclaimer_is_not_a_breaker_failure(self):
        """A HostFallback raised by a split half (W at infinity) routes to
        the host under its own reason and does NOT count a breaker failure
        — the device executed fine and merely disclaimed."""
        ds.SUPERVISOR.configure(config=ds.BreakerConfig(
            failure_threshold=1, open_cooldown_s=30.0, probe_successes=1))

        def device_fn():
            raise RuntimeError("transient")

        def half():
            raise ds.HostFallback("w_at_infinity")

        info: dict = {}
        before = _fallbacks("w_at_infinity")
        result = ds.run("t_split_hf", device_fn, host_fn=lambda: "host",
                        split_fn=lambda: [half, half], info=info)
        assert result == "host"
        assert _fallbacks("w_at_infinity") == before + 1
        assert info["fallback_reason"] == "w_at_infinity"
        assert info["split_retry"] == "host_fallback"
        # threshold=1, yet the disclaimer did not trip the breaker
        assert ds.SUPERVISOR.breaker("t_split_hf").state == "closed"

    def test_corrupt_verdict_fault(self):
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        s = make_set(b"corrupt-fault")
        assert verify_signature_sets_device([s], seed=b"t") is True
        fi.install("device.result", "corrupt", op="bls_verify", first_n=1)
        assert verify_signature_sets_device([s], seed=b"t") is False
        assert verify_signature_sets_device([s], seed=b"t") is True

    def test_breaker_trip_and_half_open_recovery(self):
        from lighthouse_tpu.ops.verify import verify_signature_sets_device

        # Cooldown far beyond what the slow host fallbacks can eat through:
        # OPEN must still be OPEN when the routed-to-host call is asserted.
        ds.SUPERVISOR.configure(config=ds.BreakerConfig(
            failure_threshold=2, open_cooldown_s=60.0, probe_successes=1))
        plan = fi.install("device.dispatch", "error", op="bls_verify")
        s = make_set(b"trip")
        fb_open_before = _fallbacks("breaker_open")
        for _ in range(2):  # two failures (split of a 1-set batch cannot help)
            assert verify_signature_sets_device([s], seed=b"t") is True
        br = ds.SUPERVISOR.breaker("bls_verify")
        assert br.state == "open"
        hits_after_trip = fi.plans()[0]["hits"]
        # OPEN: routed to host without touching the device (no new hits)
        assert verify_signature_sets_device([s], seed=b"t") is True
        assert _fallbacks("breaker_open") == fb_open_before + 1
        assert fi.plans()[0]["hits"] == hits_after_trip
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")[0]
        assert rec["breaker_state"] == "open"
        assert rec["fallback_reason"] == "breaker_open"
        # never dispatched: excluded from the occupancy tuning data
        assert "occupancy_sets" not in rec
        # clear the fault and rewind the trip instant (deterministic
        # stand-in for waiting out the cooldown): HALF_OPEN probe -> CLOSED
        fi.clear(plan_id=plan.plan_id)
        with br._lock:
            br._opened_at -= 61.0
        assert verify_signature_sets_device([s], seed=b"t") is True
        assert br.state == "closed"
        assert br.probes_total >= 1


# ------------------------------------------------------ sha / epoch parity


class TestShaAndEpochFallback:
    def test_sha_host_fallback_matches_hashlib(self):
        import hashlib

        from lighthouse_tpu.ops.sha256_device import hash_pairs_device

        data = bytes(rng.randrange(256) for _ in range(8 * 64))
        expect = b"".join(
            hashlib.sha256(data[i:i + 64]).digest()
            for i in range(0, len(data), 64)
        )
        fi.install("device.dispatch", "error", op="sha256_pairs")
        before = _fallbacks("device_error")
        assert hash_pairs_device(data) == expect
        assert _fallbacks("device_error") == before + 1
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="sha256_pairs")[0]
        assert rec["host_fallback"] is True

    def test_sha_split_retry_matches(self):
        import hashlib

        from lighthouse_tpu.ops.sha256_device import hash_pairs_device

        data = bytes(rng.randrange(256) for _ in range(8 * 64))
        expect = b"".join(
            hashlib.sha256(data[i:i + 64]).digest()
            for i in range(0, len(data), 64)
        )
        fi.install("device.dispatch", "error", op="sha256_pairs", first_n=1)
        before = metrics.DEVICE_SPLIT_RETRIES.get(
            op="sha256_pairs", outcome="success")
        assert hash_pairs_device(data) == expect
        assert metrics.DEVICE_SPLIT_RETRIES.get(
            op="sha256_pairs", outcome="success") == before + 1

    def test_epoch_device_fault_falls_back_to_numpy_exactly(self):
        from lighthouse_tpu.consensus import per_epoch as pe
        from lighthouse_tpu.consensus.genesis import interop_genesis_state
        from lighthouse_tpu.consensus.per_slot import process_slots
        from lighthouse_tpu.types.containers import build_types
        from lighthouse_tpu.types.spec import minimal_spec

        spec = minimal_spec(altair_fork_epoch=0, bellatrix_fork_epoch=0,
                            capella_fork_epoch=0)
        types = build_types(spec.preset)
        state = interop_genesis_state(32, types, spec,
                                      genesis_time=1_600_000_000)
        state = process_slots(state, spec.slots_per_epoch * 2 - 1, types, spec)
        r = random.Random(23)
        state.previous_epoch_participation = [r.randrange(8) for _ in range(32)]
        state.current_epoch_participation = [r.randrange(8) for _ in range(32)]
        state.inactivity_scores = [r.randrange(50) for _ in range(32)]

        a, b = state.copy(), state.copy()
        pe.process_epoch(a, types, spec)  # numpy golden
        fi.install("device.dispatch", "error")  # fault every dispatch
        before = _fallbacks("device_error")
        pe.set_epoch_backend("device")
        try:
            pe.process_epoch(b, types, spec)
        finally:
            pe.set_epoch_backend("numpy")
        assert _fallbacks("device_error") > before
        assert list(a.balances) == list(b.balances)
        assert a.hash_tree_root() == b.hash_tree_root()


# --------------------------------------------------- scheduler re-enqueue


class TestSchedulerRequeue:
    def test_dispatch_timeout_requeues_work_once(self):
        from lighthouse_tpu.scheduler import BeaconProcessor
        from lighthouse_tpu.scheduler.processor import WORK_EVENTS_REQUEUED
        from lighthouse_tpu.scheduler.work import RequeueWork, W, WorkEvent

        assert issubclass(ds.DispatchTimeout, RequeueWork)
        proc = BeaconProcessor(max_workers=1)
        try:
            attempts = []
            done = threading.Event()

            def handler(item):
                attempts.append(1)
                if len(attempts) == 1:
                    # escaped deadline: no host fallback available
                    ds.run("requeue_op", lambda: time.sleep(2.0),
                           host_fn=None, deadline_s=0.1)
                done.set()

            before = WORK_EVENTS_REQUEUED.get(work=W.GOSSIP_BLOCK)
            assert proc.send(WorkEvent(work_type=W.GOSSIP_BLOCK,
                                       process=handler, item=None))
            assert done.wait(10.0), "re-enqueued work never ran"
            proc.wait_idle(5.0)
            assert len(attempts) == 2
            assert WORK_EVENTS_REQUEUED.get(work=W.GOSSIP_BLOCK) == before + 1
        finally:
            proc.shutdown()

    def test_partial_batch_requeue_skips_processed_events(self):
        """A RequeueWork mid-batch re-enqueues only the raiser and the
        unprocessed tail — events that already completed must not run
        twice (duplicate fork-choice/pool side effects)."""
        from lighthouse_tpu.scheduler import BeaconProcessor
        from lighthouse_tpu.scheduler.work import RequeueWork, W, WorkEvent

        proc = BeaconProcessor(max_workers=1)
        try:
            release = threading.Event()
            calls: dict = {}

            def blocker(item):
                release.wait(10.0)

            def handler(item):
                calls[item] = calls.get(item, 0) + 1
                if item == "b" and calls[item] == 1:
                    raise RequeueWork("retry me")

            # Hold the single worker so a/b/c coalesce into one drained
            # batch (GOSSIP_ATTESTATION is batchable, process_batch unset
            # => the per-event loop runs).
            assert proc.send(WorkEvent(
                work_type=W.GOSSIP_ATTESTATION, process=blocker, item="x"))
            for it in ("a", "b", "c"):
                assert proc.send(WorkEvent(
                    work_type=W.GOSSIP_ATTESTATION, process=handler, item=it))
            release.set()
            proc.wait_idle(10.0)
            time.sleep(0.1)
            proc.wait_idle(10.0)
            # a completed before the raise: exactly once. b retried once.
            # c rode the requeued tail: exactly once.
            assert calls == {"a": 1, "b": 2, "c": 1}
        finally:
            proc.shutdown()

    def test_retries_are_bounded(self):
        from lighthouse_tpu.scheduler import BeaconProcessor
        from lighthouse_tpu.scheduler.work import RequeueWork, W, WorkEvent

        proc = BeaconProcessor(max_workers=1)
        try:
            attempts = []

            def always_requeue(item):
                attempts.append(1)
                raise RequeueWork("still broken")

            proc.send(WorkEvent(work_type=W.GOSSIP_BLOCK,
                                process=always_requeue, item=None))
            proc.wait_idle(5.0)
            time.sleep(0.1)
            proc.wait_idle(5.0)
            assert len(attempts) == 2  # original + MAX_WORK_RETRIES
            assert proc.metrics.dropped.get(W.GOSSIP_BLOCK, 0) >= 1
        finally:
            proc.shutdown()


# --------------------------------------------------------- acceptance e2e


def _walk(sp):
    yield sp
    for c in sp.children:
        yield from _walk(c)


def _http(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, body=payload)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class TestChainHarnessWithFaultedDevice:
    def test_import_survives_faulted_dispatch_then_breaker_recovers(self):
        """Acceptance (ISSUE 5): with a fault plan failing every
        ``device.dispatch`` for bls_verify, a multi-block segment imports to
        the correct head via the host path; the breaker reports OPEN on
        ``GET /lighthouse/device`` and as SSE events, then recovers to
        CLOSED after the plan is cleared and probes pass."""
        from lighthouse_tpu.chain import BeaconChainHarness
        from lighthouse_tpu.chain import events as ev
        from lighthouse_tpu.crypto.bls.backends import set_backend
        from lighthouse_tpu.http_api import HttpApiServer

        ds.SUPERVISOR.configure(config=ds.BreakerConfig(
            failure_threshold=2, open_cooldown_s=0.3, probe_successes=1))
        set_backend("jax")
        server = None
        try:
            harness = BeaconChainHarness(validator_count=8, fake_crypto=False)
            server = HttpApiServer(harness.chain).start()
            sub = harness.chain.events.subscribe([ev.TOPIC_DEVICE_BREAKER])

            # Fault every bls_verify dispatch via the admin endpoint.
            status, _ = _http(
                server.port, "POST", "/lighthouse/faults",
                body={"spec": "device.dispatch[op=bls_verify]=error"})
            assert status == 200

            roots = harness.extend_chain(2, attest=False)
            assert harness.chain.head_root == roots[-1], (
                "chain must reach the correct head on the host path")
            br = ds.SUPERVISOR.breaker("bls_verify")
            assert br.state == "open"
            assert metrics.DEVICE_BREAKER_STATE.get(op="bls_verify") == 1

            # Visible on the operator surface.
            status, out = _http(server.port, "GET", "/lighthouse/device")
            assert status == 200
            sup = out["data"]["supervisor"]
            bls = next(b for b in sup["breakers"] if b["op"] == "bls_verify")
            assert bls["state"] == "open" and bls["trips_total"] >= 1
            assert "bls_verify" in sup["deadlines_s"]
            fallbacks = out["data"]["host_fallbacks"]
            assert fallbacks.get("device_error", 0) >= 2

            # SSE: the closed->open transition reached the event bus.
            events = []
            while True:
                item = sub.poll(timeout=0.05)
                if item is None:
                    break
                events.append(item[1])
            assert any(
                e["op"] == "bls_verify" and e["to"] == "open" for e in events)

            # Clear the plan (admin endpoint), wait out the cooldown: the
            # next import probes the device, passes, and the breaker closes.
            status, out = _http(server.port, "DELETE", "/lighthouse/faults")
            assert status == 200 and out["data"]["cleared"] == 1
            time.sleep(0.35)
            roots = harness.extend_chain(1, attest=False)
            assert harness.chain.head_root == roots[-1]
            assert br.state == "closed"
            assert metrics.DEVICE_BREAKER_STATE.get(op="bls_verify") == 0
            events = []
            while True:
                item = sub.poll(timeout=0.05)
                if item is None:
                    break
                events.append(item[1])
            assert [e["to"] for e in events if e["op"] == "bls_verify"] == [
                "half_open", "closed"]
        finally:
            if server is not None:
                server.stop()
            set_backend("host")

    def test_flight_record_and_trace_stamp_breaker_state(self):
        """Host-fallback batches stamp reason + breaker state onto both the
        flight-recorder record and the enclosing trace."""
        from lighthouse_tpu import tracing
        from lighthouse_tpu.crypto.bls.backends import jax_backend

        fi.install("device.dispatch", "error", op="bls_verify", first_n=1)
        s = make_set(b"stamp")
        with tracing.span("import_root") as root:
            assert jax_backend.verify_signature_sets([s], seed=b"t") is True
        dv = next(sp for sp in _walk(root) if sp.name == "device_verify")
        assert dv.fields.get("host_fallback") is True
        assert dv.fields["fallback_reason"] == "device_error"
        rec = device_telemetry.FLIGHT_RECORDER.recent(
            trace_id=root.trace.trace_id)[0]
        assert rec["host_fallback"] is True
        assert rec["fallback_reason"] == "device_error"
