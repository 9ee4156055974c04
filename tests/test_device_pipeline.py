"""Async device pipeline (ISSUE 8): cross-work-type coalescing, per-group
verdict attribution, linger-deadline flush, breaker-open host routing with
futures still resolving, clean shutdown drain, and the api-seam wiring."""

import threading
import time

import pytest

from lighthouse_tpu import device_pipeline, device_supervisor, metrics
from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.crypto.bls.backends import set_backend
from lighthouse_tpu.device_pipeline import DevicePipeline, PipelineShutdown


@pytest.fixture(autouse=True)
def _clean():
    device_pipeline.reset_for_tests()
    yield
    device_pipeline.reset_for_tests()
    device_supervisor.reset_for_tests()
    set_backend("host")


def _sets(n, seed=1, message=b"m" * 32):
    """n valid single-key signature sets (host crypto, distinct keys)."""
    out = []
    for i in range(n):
        sk = api.SecretKey(seed + i * 7919)
        out.append(api.SignatureSet.single_pubkey(
            sk.sign(message), sk.public_key(), message))
    return out


def _bad_set(seed=99):
    """Valid points, wrong message: builds fine, verifies False."""
    sk = api.SecretKey(seed)
    return api.SignatureSet.single_pubkey(
        sk.sign(b"signed-this" * 3), sk.public_key(), b"claims-this" * 3)


class _StubSet:
    """Structurally valid for the fake backend (which checks signing_keys)."""

    signing_keys = [1]


class GatedVerify:
    """verify_flat_fn test seam: blocks each batch until released."""

    def __init__(self, verdict=True):
        self.gate = threading.Event()
        self.verdict = verdict
        self.batches = []

    def __call__(self, flat_sets):
        self.batches.append(list(flat_sets))
        assert self.gate.wait(10.0)
        return self.verdict


# ------------------------------------------------- per-group attribution


class TestGroupVerdicts:
    def test_mixed_batch_attributes_per_group(self):
        """One bad group inside a coalesced batch: the batch verdict is
        False, each group gets ONE host re-check, and only the bad group's
        future resolves False."""
        set_backend("host")
        pipe = DevicePipeline("bls_verify", target_sets=8, linger_s=0.5)
        try:
            good = pipe.submit(_sets(1, seed=5), work="gossip_attestation")
            bad = pipe.submit([_bad_set()], work="block_import")
            assert good.result(timeout=30.0) is True
            assert bad.result(timeout=30.0) is False
            snap = pipe.snapshot()
            # both groups rode ONE coalesced batch, attributed by re-check
            assert snap["batches_total"] == 1
            rec = snap["recent_batches"][-1]
            assert rec["n_groups"] == 2
            assert rec["verdict"] is False
            assert rec["group_rechecks"] == 2
            assert rec["work_mix"] == {"gossip_attestation": 1,
                                       "block_import": 1}
        finally:
            pipe.shutdown()

    def test_single_group_batch_needs_no_recheck(self):
        set_backend("host")
        pipe = DevicePipeline("bls_verify", target_sets=8, linger_s=0.02)
        try:
            fut = pipe.submit([_bad_set()])
            assert fut.result(timeout=30.0) is False
            rec = pipe.snapshot()["recent_batches"][-1]
            assert rec["n_groups"] == 1 and rec["group_rechecks"] == 0
        finally:
            pipe.shutdown()

    def test_empty_group_resolves_false_immediately(self):
        pipe = DevicePipeline("bls_verify", verify_flat_fn=lambda s: True)
        try:
            fut = pipe.submit([])
            assert fut.done() and fut.result(0.0) is False
        finally:
            pipe.shutdown()


# ------------------------------------------------ cross-work-type coalescing


class TestCoalescing:
    def test_cross_work_type_batch_reaches_target_under_load(self):
        """While one batch is in flight, groups from different work types
        pile up and the next take is a full target-sized batch."""
        gated = GatedVerify()
        pipe = DevicePipeline("bls_verify", target_sets=32, linger_s=0.3,
                              verify_flat_fn=gated)
        try:
            kinds = ["block_import", "gossip_attestation", "gossip_aggregate",
                     "sync_committee"]
            first = pipe.submit(["w"], work="warm")  # occupies the executor
            deadline = time.monotonic() + 5
            while not gated.batches and time.monotonic() < deadline:
                time.sleep(0.005)  # wait until the warm batch is IN FLIGHT
            assert gated.batches, "warm batch never reached the executor"
            futs = []
            for i in range(40):
                futs.append(pipe.submit([f"s{i}"], work=kinds[i % len(kinds)]))
            gated.gate.set()
            assert first.result(10.0) is True
            for f in futs:
                assert f.result(10.0) is True
            snap = pipe.snapshot()
            full = [b for b in snap["recent_batches"] if b["n_sets"] == 32]
            assert full, f"no full batch formed: {snap['recent_batches']}"
            assert len(full[0]["work_mix"]) == len(kinds)
            assert pipe.wait_idle(5.0)
        finally:
            pipe.shutdown()

    def test_group_never_splits_across_batches(self):
        """A group is atomic: packing stops before target overflow, except a
        lone oversized-vs-target group which dispatches alone."""
        gated = GatedVerify()
        pipe = DevicePipeline("bls_verify", target_sets=4, linger_s=0.3,
                              verify_flat_fn=gated)
        try:
            first = pipe.submit(["w"], work="warm")
            deadline = time.monotonic() + 5
            while not gated.batches and time.monotonic() < deadline:
                time.sleep(0.005)
            assert gated.batches, "warm batch never reached the executor"
            f3 = pipe.submit(["a", "b", "c"])
            f2 = pipe.submit(["d", "e"])
            gated.gate.set()
            assert first.result(10.0) and f3.result(10.0) and f2.result(10.0)
            sizes = [b["n_sets"] for b in pipe.snapshot()["recent_batches"]]
            # 3 doesn't fit with 2 under target 4: two separate batches
            assert sizes[:1] == [1] and sorted(sizes[1:]) == [2, 3]
        finally:
            pipe.shutdown()

    def test_linger_deadline_flushes_lone_set(self):
        """A lone attestation never waits for a full bucket: the linger
        window bounds its latency."""
        pipe = DevicePipeline("bls_verify", target_sets=4096, linger_s=0.05,
                              verify_flat_fn=lambda s: True)
        try:
            t0 = time.perf_counter()
            fut = pipe.submit(["solo"], work="gossip_attestation")
            assert fut.result(timeout=5.0) is True
            elapsed = time.perf_counter() - t0
            assert elapsed < 2.0, f"lone set waited {elapsed}s"
            rec = pipe.snapshot()["recent_batches"][-1]
            assert rec["n_sets"] == 1
            # it really lingered (waited for company) before dispatching
            assert rec["linger_s"] >= 0.04
        finally:
            pipe.shutdown()


class TestBuildFailure:
    def test_build_error_resolves_lone_valid_group_via_host(self, monkeypatch):
        """A transient device-build error must NOT surface as 'bad
        signature': even a LONE group re-checks on the host golden model
        (review fix: the old single-group short-circuit falsified it)."""
        from lighthouse_tpu.ops import verify as verify_mod

        set_backend("jax")

        def boom(sets, seed=None):
            raise RuntimeError("injected build failure")

        monkeypatch.setattr(verify_mod, "build_device_batch", boom)
        pipe = DevicePipeline("bls_verify", target_sets=8, linger_s=0.02)
        try:
            good = pipe.submit(_sets(1, seed=21), work="block_import")
            assert good.result(timeout=30.0) is True
            rec = pipe.snapshot()["recent_batches"][-1]
            assert rec["group_rechecks"] == 1
        finally:
            pipe.shutdown()

    def test_target_clamped_to_dispatch_ceiling(self):
        pipe = DevicePipeline("bls_verify", target_sets=999_999,
                              verify_flat_fn=lambda s: True)
        try:
            assert pipe.target_sets <= device_pipeline.MAX_GROUP_SETS <= 4096
        finally:
            pipe.shutdown()

    def test_module_verify_refuses_resurrection_after_shutdown(self):
        """verify() racing shutdown() must raise PipelineShutdown (the api
        seam falls back to the direct path), never spawn a fresh pipeline."""
        device_pipeline.enable()
        device_pipeline.get_pipeline()
        device_pipeline.shutdown()  # disables + nulls the singleton
        with pytest.raises(PipelineShutdown):
            device_pipeline.verify([_StubSet()])
        assert device_pipeline.summary() is None  # nothing resurrected


# --------------------------------------------------- breaker-open routing


class TestBreakerOpen:
    def test_breaker_open_routes_to_host_and_futures_resolve(self):
        """With the bls_verify breaker OPEN, a pipeline batch routes to the
        host golden model without touching the device — and every group's
        future still resolves with its correct verdict."""
        set_backend("jax")  # device mode: execute_built_batch path
        device_supervisor.SUPERVISOR.configure(
            config=device_supervisor.BreakerConfig(
                failure_threshold=1, open_cooldown_s=300.0))
        br = device_supervisor.SUPERVISOR.breaker("bls_verify")
        br.record_failure("device_error")
        assert device_supervisor.breaker_state("bls_verify") == "open"
        before = metrics.DEVICE_HOST_FALLBACK.get(reason="breaker_open")
        good_sets = _sets(1, seed=11)   # built BEFORE submit: signing is
        bad_sets = [_bad_set(seed=13)]  # slow, and both must coalesce
        pipe = DevicePipeline("bls_verify", target_sets=8, linger_s=0.5)
        try:
            good = pipe.submit(good_sets, work="block_import")
            bad = pipe.submit(bad_sets, work="gossip_attestation")
            assert good.result(timeout=60.0) is True
            assert bad.result(timeout=60.0) is False
            after = metrics.DEVICE_HOST_FALLBACK.get(reason="breaker_open")
            assert after == before + 1
            assert pipe.snapshot()["batches_total"] == 1
            assert device_supervisor.breaker_state("bls_verify") == "open"
        finally:
            pipe.shutdown()


# ------------------------------------------------------- shutdown drain


class TestShutdown:
    def test_shutdown_drains_pending_futures(self):
        gated = GatedVerify()
        pipe = DevicePipeline("bls_verify", target_sets=4, linger_s=5.0,
                              verify_flat_fn=gated)
        first = pipe.submit(["w"], work="warm")
        pending = [pipe.submit([f"p{i}"]) for i in range(6)]
        done = threading.Event()

        def stop():
            pipe.shutdown()
            done.set()

        t = threading.Thread(target=stop, daemon=True)
        t.start()
        gated.gate.set()
        assert done.wait(15.0), "shutdown hung"
        assert first.result(1.0) is True
        for f in pending:
            assert f.result(1.0) is True
        assert pipe.wait_idle(1.0)
        with pytest.raises(PipelineShutdown):
            pipe.submit(["late"])

    def test_module_shutdown_is_idempotent_and_disables(self):
        device_pipeline.enable()
        assert device_pipeline.enabled()
        device_pipeline.get_pipeline()
        device_pipeline.shutdown()
        assert not device_pipeline.enabled()
        device_pipeline.shutdown()  # second call: no-op


# ------------------------------------------------------------ api seam


class TestApiSeam:
    def test_verify_signature_sets_routes_through_pipeline(self):
        set_backend("fake")
        device_pipeline.enable()
        assert api.verify_signature_sets([_StubSet()]) is True
        snap = device_pipeline.summary()
        assert snap is not None and snap["batches_total"] >= 1

    def test_seeded_and_oversized_calls_bypass_pipeline(self):
        set_backend("fake")
        device_pipeline.enable()
        api.verify_signature_sets([_StubSet()], seed=b"pinned")
        big = [_StubSet()] * (device_pipeline.MAX_GROUP_SETS + 1)
        api.verify_signature_sets(big)
        # neither call started (or fed) a pipeline
        assert device_pipeline.summary() is None

    def test_disabled_routes_nothing(self):
        set_backend("fake")
        assert not device_pipeline.enabled()
        api.verify_signature_sets([_StubSet()])
        assert device_pipeline.summary() is None


# ------------------------------------------------------------- telemetry


class TestTelemetry:
    def test_metrics_and_summary_sections(self):
        pipe = DevicePipeline("bls_verify", target_sets=8, linger_s=0.02,
                              verify_flat_fn=lambda s: True)
        try:
            pipe.submit(["a"], work="block_import").result(5.0)
            assert metrics.DEVICE_PIPELINE_BATCHES.get(op="bls_verify") >= 1
            assert metrics.DEVICE_PIPELINE_GROUPS.get(
                op="bls_verify", work="block_import") >= 1
            n, total = metrics.DEVICE_PIPELINE_BATCH_FILL_RATIO.stats(
                op="bls_verify")
            assert n >= 1
            n, _ = metrics.DEVICE_PIPELINE_LINGER_SECONDS.stats(op="bls_verify")
            assert n >= 1
        finally:
            pipe.shutdown()

    def test_device_summary_carries_pipeline_section(self):
        from lighthouse_tpu import device_telemetry

        assert device_telemetry.summary()["pipeline"] is None
        device_pipeline.get_pipeline()
        section = device_telemetry.summary()["pipeline"]
        assert section is not None and section["op"] == "bls_verify"

    def test_flight_record_carries_groups_and_work_mix(self):
        from lighthouse_tpu import device_telemetry

        device_telemetry.record_batch(
            op="bls_verify", shape=(8, 2), n_live=5, n_groups=3,
            work_mix={"block_import": 4, "gossip_attestation": 1})
        rec = device_telemetry.FLIGHT_RECORDER.recent(limit=1)[0]
        assert rec["n_groups"] == 3
        assert rec["work_mix"]["block_import"] == 4


# ------------------------------------------------ one trace per request


def _tree(sp, parent=None, out=None):
    """span name -> [(span, parent name)], over the whole tree."""
    out = {} if out is None else out
    out.setdefault(sp.name, []).append((sp, parent))
    for child in sp.children:
        _tree(child, sp.name, out)
    return out


class TestOneTracePerRequest:
    def test_pipeline_verify_yields_one_tree(self):
        """The pipeline twin of test_device_chain's stage test: a block's
        verify_signature_sets through the pipeline is ONE trace, holding the
        caller's wait, the builder's marshal and the executor's device legs."""
        from lighthouse_tpu import tracing

        sets = _sets(2, seed=31)
        set_backend("jax")
        device_pipeline.enable()
        tracing.TRACES.clear()
        assert api.verify_signature_sets(sets) is True
        device_pipeline.get_pipeline().wait_idle(30.0)
        roots = {t.root.name for t in tracing.TRACES.recent(limit=100)}
        assert "pipeline_build" not in roots and "pipeline_batch" not in roots
        trace = tracing.TRACES.recent(root="device_batch")[0]
        tree = _tree(trace.root)
        assert tree["pipeline_wait"][0][1] == "device_batch"
        assert tree["pipeline_build"][0][1] == "device_batch"
        assert tree["device_batch_setup"][0][1] == "pipeline_build"
        setup = tree["device_batch_setup"][0][0]
        assert [c.name for c in setup.children] == [
            "marshal_subgroup", "marshal_hash_to_g2", "marshal_pack",
            "marshal_upload"]
        assert tree["pipeline_batch"][0][1] == "device_batch"
        for leg in ("device_batch_dispatch", "device_batch_wait",
                    "device_batch_verdict"):
            assert tree[leg][0][1] == "pipeline_batch", leg
        # the batch's flight record names the block's trace
        from lighthouse_tpu import device_telemetry

        rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify", limit=1)[0]
        assert rec["trace_id"] == trace.trace_id

    def test_coalesced_groups_link_their_traces(self):
        """Two groups from two traces in one batch: the batch span opens in
        the oldest group's trace and names the other's."""
        from lighthouse_tpu import tracing

        pipe = DevicePipeline("bls_verify", target_sets=2, linger_s=5.0,
                              verify_flat_fn=lambda s: True)
        try:
            with tracing.span("unit_first") as first:
                f1 = pipe.submit(["a"])
            with tracing.span("unit_second") as second:
                f2 = pipe.submit(["b"])
            assert f1.result(10.0) is True and f2.result(10.0) is True
            assert pipe.wait_idle(10.0)
        finally:
            pipe.shutdown()
        batch = [c for c in first.children if c.name == "pipeline_batch"]
        assert len(batch) == 1
        assert batch[0].fields["n_groups"] == 2
        assert batch[0].fields["linked_trace_ids"] == [second.trace.trace_id]
        assert not [c for c in second.children if c.name == "pipeline_batch"]


# ----------------------------------------- hash pipeline (ISSUE 13)


class TestHashPipeline:
    def test_groups_coalesce_with_exact_slice_attribution(self):
        """Unequal-size groups coalesce into one batch; each future's
        digests are the exact slice for its blocks (bit-identical to
        hashing the group alone)."""
        from lighthouse_tpu.device_pipeline import HashPipeline
        from lighthouse_tpu.ops.tree_hash import golden_hash_pairs

        pipe = HashPipeline(target_blocks=64, linger_s=0.5,
                            hash_flat_fn=golden_hash_pairs)
        try:
            groups = [bytes([i]) * (64 * k) for i, k in
                      ((1, 1), (2, 3), (3, 2))]
            futs = [pipe.submit(g, work=f"w{i}")
                    for i, g in enumerate(groups)]
            for g, fut in zip(groups, futs):
                assert fut.result(timeout=30.0) == golden_hash_pairs(g)
            snap = pipe.snapshot()
            assert snap["batches_total"] == 1  # one coalesced dispatch
            assert snap["groups_total"] == 3
            assert snap["blocks_total"] == 6
            rec = snap["recent_batches"][-1]
            assert rec["n_groups"] == 3 and rec["n_blocks"] == 6
            assert rec["work_mix"] == {"w0": 1, "w1": 3, "w2": 2}
        finally:
            pipe.shutdown()

    def test_flat_failure_rescues_each_group_on_host(self):
        """A failure escaping the supervised leg re-hashes per group on the
        host kernel — digests stay exact, nothing is corrupted."""
        from lighthouse_tpu.device_pipeline import HashPipeline
        from lighthouse_tpu.ops.tree_hash import golden_hash_pairs

        def poisoned(data):
            raise RuntimeError("flat leg poisoned")

        pipe = HashPipeline(target_blocks=64, linger_s=0.2,
                            hash_flat_fn=poisoned)
        try:
            groups = [b"\xaa" * 64, b"\xbb" * 128]
            futs = [pipe.submit(g) for g in groups]
            for g, fut in zip(groups, futs):
                assert fut.result(timeout=30.0) == golden_hash_pairs(g)
            rec = pipe.snapshot()["recent_batches"][-1]
            assert rec["group_rehashes"] == 2
        finally:
            pipe.shutdown()

    def test_misaligned_group_rejected_and_empty_resolves(self):
        from lighthouse_tpu.device_pipeline import HashPipeline
        from lighthouse_tpu.ops.tree_hash import golden_hash_pairs

        pipe = HashPipeline(target_blocks=8, linger_s=0.01,
                            hash_flat_fn=golden_hash_pairs)
        try:
            with pytest.raises(ValueError):
                pipe.submit(b"x" * 63)
            fut = pipe.submit(b"")
            assert fut.done() and fut.result(0.0) == b""
        finally:
            pipe.shutdown()

    def test_module_hash_seam_and_shutdown_fallback(self):
        """routes_hash gates on enablement and size; after shutdown the
        module seam raises PipelineShutdown (callers fall back direct)."""
        from lighthouse_tpu.ops.tree_hash import golden_hash_pairs

        assert not device_pipeline.routes_hash(16)  # disabled
        device_pipeline.enable()
        assert device_pipeline.routes_hash(16)
        assert not device_pipeline.routes_hash(
            device_pipeline.MAX_HASH_GROUP_BLOCKS + 1)
        data = b"\x5a" * 256
        # module-level hash_pairs lazily starts the pipeline and resolves
        assert device_pipeline.hash_pairs(data) == golden_hash_pairs(data)
        snap = device_pipeline.summary()
        assert snap["hash"]["groups_total"] >= 1
        assert snap["arbiter"]["grants"].get("sha256_pairs", 0) >= 1
        device_pipeline.shutdown()
        with pytest.raises(PipelineShutdown):
            device_pipeline.hash_pairs(data)


# ------------------------------------------ job pipeline (ISSUE 13)


class TestJobPipeline:
    def test_epoch_deltas_routes_through_job_pipeline(self):
        """The per_epoch device path rides run_job when the pipeline is on:
        same arrays as the numpy golden, a job accounted on the epoch op,
        and an arbiter grant for it."""
        import numpy as np

        from lighthouse_tpu.consensus import per_epoch
        from test_epoch_buckets import _registry

        arrays, prev_part, inact, kw = _registry(48, seed=31)
        golden = per_epoch._epoch_deltas_numpy(arrays, prev_part, inact, **kw)
        device_pipeline.enable()
        per_epoch.set_epoch_backend("device")
        try:
            out = per_epoch.epoch_deltas(arrays, prev_part, inact, **kw)
        finally:
            per_epoch.set_epoch_backend("numpy")
        for g, d in zip(golden, out):
            assert np.array_equal(g, d)
        snap = device_pipeline.summary()
        assert snap["jobs"]["epoch_deltas"]["jobs_total"] == 1
        assert snap["jobs"]["epoch_deltas"]["pending_jobs"] == 0
        assert snap["arbiter"]["grants"].get("epoch_deltas", 0) == 1

    def test_breaker_open_job_still_routes_to_host_exactly(self):
        """Breaker open on the epoch op + pipeline on: the job runs, the
        supervisor inside it routes to the numpy host path, and the result
        is still exact (attribution preserved through the pipeline)."""
        import numpy as np

        from lighthouse_tpu.consensus import per_epoch
        from test_epoch_buckets import _registry

        device_supervisor.SUPERVISOR.configure(
            config=device_supervisor.BreakerConfig(
                failure_threshold=1, open_cooldown_s=300.0))
        device_supervisor.SUPERVISOR.breaker("epoch_deltas").record_failure(
            "device_error")
        assert device_supervisor.breaker_state("epoch_deltas") == "open"

        arrays, prev_part, inact, kw = _registry(40, seed=37)
        golden = per_epoch._epoch_deltas_numpy(arrays, prev_part, inact, **kw)
        before = metrics.DEVICE_HOST_FALLBACK.get(reason="breaker_open")
        device_pipeline.enable()
        per_epoch.set_epoch_backend("device")
        try:
            out = per_epoch.epoch_deltas(arrays, prev_part, inact, **kw)
        finally:
            per_epoch.set_epoch_backend("numpy")
        for g, d in zip(golden, out):
            assert np.array_equal(g, d)
        assert metrics.DEVICE_HOST_FALLBACK.get(
            reason="breaker_open") == before + 1
        assert device_pipeline.summary()["jobs"]["epoch_deltas"][
            "jobs_total"] == 1

    def test_job_error_propagates_and_shutdown_refuses(self):
        device_pipeline.enable()
        with pytest.raises(RuntimeError, match="job boom"):
            device_pipeline.run_job(
                "epoch_deltas", lambda: (_ for _ in ()).throw(
                    RuntimeError("job boom")))
        device_pipeline.shutdown()
        with pytest.raises(PipelineShutdown):
            device_pipeline.run_job("epoch_deltas", lambda: 1)


# ---------------------------------------- adaptive linger (ISSUE 13)


class TestAdaptiveLinger:
    def test_pinned_and_unobserved_return_base(self):
        from lighthouse_tpu.device_pipeline import effective_linger

        assert effective_linger("linger_op_a", 0.02, pinned=True) == 0.02
        # no flight-recorder samples for this op -> base
        assert effective_linger("linger_op_a", 0.02, pinned=False) == 0.02

    def test_tracks_observed_inflight_median_with_clamps(self):
        from lighthouse_tpu import device_telemetry
        from lighthouse_tpu.device_pipeline import effective_linger

        for _ in range(4):
            device_telemetry.record_batch(
                op="linger_op_b", shape=(8,), n_live=8,
                stages={"dispatch": 0.05, "wait": 0.15})
        # median in-flight 0.2s -> half is 0.1, above the 0.02 floor
        assert effective_linger("linger_op_b", 0.02, pinned=False) == \
            pytest.approx(0.1)
        for _ in range(8):
            device_telemetry.record_batch(
                op="linger_op_c", shape=(8,), n_live=8,
                stages={"dispatch": 5.0, "wait": 5.0})
        # pathological observation clamps at the max
        assert effective_linger("linger_op_c", 0.02, pinned=False) == \
            device_pipeline.ADAPTIVE_LINGER_MAX_S
        # a fast device never erases the configured floor
        for _ in range(4):
            device_telemetry.record_batch(
                op="linger_op_d", shape=(8,), n_live=8,
                stages={"dispatch": 0.001, "wait": 0.001})
        assert effective_linger("linger_op_d", 0.05, pinned=False) == 0.05

    def test_host_fallback_and_compile_batches_do_not_feed_the_signal(self):
        """Host fallbacks never saw the device; compile batches carry jit
        time in their dispatch stage (minutes on CPU) — neither belongs in
        a steady-state linger signal."""
        from lighthouse_tpu import device_telemetry

        for _ in range(4):
            device_telemetry.record_batch(
                op="linger_op_e", shape=(8,), n_live=8,
                stages={"dispatch": 0.2, "wait": 0.2}, host_fallback=True)
        assert device_telemetry.recent_inflight_seconds("linger_op_e") is None
        for _ in range(4):
            device_telemetry.record_batch(
                op="linger_op_f", shape=(8,), n_live=8,
                stages={"dispatch": 60.0, "wait": 0.01}, compiled=True)
        assert device_telemetry.recent_inflight_seconds("linger_op_f") is None

    def test_assignment_pins_the_pipeline_linger(self):
        pipe = DevicePipeline("bls_verify", target_sets=8, linger_s=None,
                              verify_flat_fn=lambda s: True)
        try:
            snap = pipe.snapshot()
            assert snap["linger_adaptive"] is True
            pipe.linger_s = 0.07
            snap = pipe.snapshot()
            assert snap["linger_adaptive"] is False
            assert snap["effective_linger_s"] == pytest.approx(0.07)
        finally:
            pipe.shutdown()
