"""Self-tuning device runtime (ISSUE 15): the autotune controller's
guardrails, the pinned replay, the measured fq-backend cache, and the
latency-driven admission bounds.

Tier-1 discipline: everything here is host-side control-plane logic — no
device dispatch, no XLA compile (the one real-warmup test is slow-marked).
The guardrail tests are the acceptance-critical ones: a bucket must never
be adopted without a committed hlo_budget entry, and never (in live mode)
before its off-path AOT warmup completes.
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from lighthouse_tpu import autotune, device_telemetry
from lighthouse_tpu.scheduler.admission import (
    CLASS_BULK,
    AdmissionController,
    ClassPolicy,
    ShedError,
)

#: a committed baseline key (regenerated this PR) the warmup tests lean on
BUDGETED_SHA_KEY = "sha256_pairs|-|640|-"


@pytest.fixture(autouse=True)
def _clean():
    autotune.reset_for_tests()
    device_telemetry.reset_for_tests()
    yield
    # synthetic vocabularies registered by a test must not leak into the
    # rest of the suite; real registrations (no "t_" prefix) mirror module
    # imports — an ops module first imported MID-test must keep its entry
    for name in [n for n in autotune._VOCABS if n.startswith("t_")]:
        autotune._VOCABS.pop(name, None)
    autotune.reset_for_tests()
    device_telemetry.reset_for_tests()


def _feed_batches(op: str, nb: int, n_live: int, count: int) -> None:
    """Flight-recorder evidence: ``count`` dispatched batches of ``op`` at
    bucket ``nb`` with ``n_live`` live rows each."""
    for _ in range(count):
        device_telemetry.record_batch(op=op, shape=(nb,), n_live=n_live)


def _register(name: str, static, budget_key, warmup=None, op=None):
    autotune.register_vocabulary(
        name, static, telemetry_ops=(op or name,),
        budget_key=budget_key, warmup=warmup)
    return autotune._VOCABS[name]


# ------------------------------------------------------------ vocabulary


class TestBucketVocabulary:
    def test_off_path_returns_static_untouched(self):
        static = (256, 1024)
        assert autotune.bucket_vocabulary("nothing", static) is static

    def test_overlay_merges_sorted_and_mode_zero_disables(self):
        _register("t_vocab", (256, 1024), lambda nb: "k")
        autotune.set_mode("live")
        autotune._set_overlay("t_vocab", (640,))
        assert autotune.bucket_vocabulary("t_vocab", (256, 1024)) == (
            256, 640, 1024)
        # mode 0 restores static behavior even with an overlay installed
        autotune.set_mode("0")
        assert autotune.bucket_vocabulary("t_vocab", (256, 1024)) == (
            256, 1024)

    def test_sha_bucket_function_consults_overlay(self):
        from lighthouse_tpu.ops import sha256_device

        assert sha256_device._bucket(500) == 1024
        autotune.set_mode("live")
        autotune._set_overlay("sha256_pairs", (640,))
        assert sha256_device._bucket(500) == 640
        assert sha256_device._bucket(700) == 1024
        autotune.reset_for_tests()
        assert sha256_device._bucket(500) == 1024


# ------------------------------------------------------------- guardrails


class TestAdoptionGuardrails:
    def test_no_adoption_without_hlo_budget_entry(self):
        """The static-gate honesty rule: a candidate with no committed
        budget key is refused, in live AND pinned mode."""
        _register("t_nobudget", (256, 1024),
                  lambda nb: f"t_nobudget|-|{nb}|-",
                  warmup=lambda nb: None)
        autotune.set_mode("live")
        _feed_batches("t_nobudget", 1024, 300, 12)
        decisions = autotune.CONTROLLER.evaluate()
        refusals = [d for d in decisions
                    if d.get("vocab") == "t_nobudget"]
        assert refusals and refusals[0]["outcome"] == "refused_no_budget"
        assert refusals[0]["bucket"] == 640
        assert autotune.overlay() == {}
        # pinned replay hits the same wall
        autotune.reset_for_tests()
        autotune.set_mode("pinned")
        autotune.CONTROLLER.install_pin([
            {"after_evaluation": 1, "vocab": "t_nobudget",
             "action": "adopt", "bucket": 640}])
        (d,) = autotune.CONTROLLER.evaluate()
        assert d["outcome"] == "refused_no_budget"
        assert autotune.overlay() == {}

    def test_no_adoption_before_warmup_completes(self):
        """Live adoption waits for the off-path AOT warmup: evaluation 1
        kicks the compile, later evaluations defer while it runs, and
        only a COMPLETED warmup adopts."""
        gate = threading.Event()
        started = threading.Event()

        def slow_warmup(nb):
            started.set()
            assert gate.wait(10), "test never released the warmup"

        _register("t_warm", (256, 1024),
                  lambda nb: BUDGETED_SHA_KEY, warmup=slow_warmup)
        autotune.set_mode("live")
        _feed_batches("t_warm", 1024, 300, 12)
        (d1,) = [d for d in autotune.CONTROLLER.evaluate()
                 if d.get("vocab") == "t_warm"]
        assert d1["outcome"] == "warmup_started"
        assert autotune.overlay() == {}, "adopted before the compile"
        assert started.wait(5)
        (d2,) = [d for d in autotune.CONTROLLER.evaluate()
                 if d.get("vocab") == "t_warm"]
        assert d2["outcome"] == "warmup_pending"
        assert autotune.overlay() == {}
        gate.set()
        deadline = time.time() + 5
        while time.time() < deadline:
            done = [d for d in autotune.CONTROLLER.evaluate()
                    if d.get("vocab") == "t_warm"]
            if done and done[0]["outcome"] == "adopted":
                break
            time.sleep(0.05)
        assert autotune.overlay().get("t_warm") == (640,)

    def test_failed_warmup_refuses_forever(self):
        def broken_warmup(nb):
            raise RuntimeError("compiler exploded")

        _register("t_broken", (256, 1024),
                  lambda nb: BUDGETED_SHA_KEY, warmup=broken_warmup)
        autotune.set_mode("live")
        _feed_batches("t_broken", 1024, 300, 12)
        autotune.CONTROLLER.evaluate()  # kicks the warmup
        deadline = time.time() + 5
        outcome = None
        while time.time() < deadline:
            got = [d for d in autotune.CONTROLLER.evaluate()
                   if d.get("vocab") == "t_broken"]
            if got and got[0]["outcome"] == "refused_warmup_failed":
                outcome = got[0]["outcome"]
                break
            time.sleep(0.05)
        assert outcome == "refused_warmup_failed"
        assert autotune.overlay() == {}

    def test_meshed_adoption_refused(self, monkeypatch):
        """With the mesh enabled, an adoption would compile an unwarmed,
        unbudgeted SHARDED executable on-path — refused until the TPU
        round lands mesh-aware warmup + |dpN| budget keys."""
        from lighthouse_tpu import device_mesh

        monkeypatch.setattr(device_mesh, "enabled", lambda: True)
        monkeypatch.setattr(device_mesh, "size", lambda: 8)
        _register("t_mesh", (256, 1024), lambda nb: BUDGETED_SHA_KEY,
                  warmup=lambda nb: None)
        autotune.set_mode("pinned")
        autotune.CONTROLLER.install_pin([
            {"after_evaluation": 1, "vocab": "t_mesh",
             "action": "adopt", "bucket": 640}])
        (d,) = autotune.CONTROLLER.evaluate()
        assert d["outcome"] == "refused_meshed"
        assert autotune.overlay() == {}

    def test_above_static_top_refused(self):
        _register("t_top", (256, 1024), lambda nb: BUDGETED_SHA_KEY,
                  warmup=lambda nb: None)
        autotune.set_mode("pinned")
        autotune.CONTROLLER.install_pin([
            {"after_evaluation": 1, "vocab": "t_top",
             "action": "adopt", "bucket": 2048}])
        (d,) = autotune.CONTROLLER.evaluate()
        assert d["outcome"] == "refused_above_top"
        assert autotune.overlay() == {}

    def test_densify_skips_ratio2_vocabularies(self):
        """A pure power-of-two vocabulary has no real gaps: quantization
        cannot waste over half, so low occupancy is a traffic question and
        the controller must suggest nothing (bucket_tuning parity)."""
        _register("t_pow2", (256, 512, 1024), lambda nb: BUDGETED_SHA_KEY,
                  warmup=lambda nb: None)
        autotune.set_mode("live")
        _feed_batches("t_pow2", 512, 100, 12)
        assert [d for d in autotune.CONTROLLER.evaluate()
                if d.get("vocab") == "t_pow2"] == []


# ---------------------------------------------------------- pinned replay


class TestPinnedReplay:
    def test_pin_applies_at_exact_evaluation_indices(self):
        _register("t_pin", (256, 1024), lambda nb: BUDGETED_SHA_KEY)
        autotune.set_mode("pinned")
        autotune.CONTROLLER.install_pin([
            {"after_evaluation": 2, "vocab": "t_pin",
             "action": "adopt", "bucket": 640},
            {"after_evaluation": 4, "vocab": "t_pin",
             "action": "drop", "bucket": 640},
        ])
        assert autotune.CONTROLLER.evaluate() == []          # eval 1
        (d2,) = autotune.CONTROLLER.evaluate()               # eval 2
        assert (d2["outcome"], d2["via"]) == ("adopted", "pin")
        assert autotune.overlay() == {"t_pin": (640,)}
        assert autotune.CONTROLLER.evaluate() == []          # eval 3
        (d4,) = autotune.CONTROLLER.evaluate()               # eval 4
        assert d4["outcome"] == "dropped"
        assert autotune.overlay() == {}
        # the whole trajectory exports back as the same pin
        assert autotune.CONTROLLER.export_pin() == [
            {"after_evaluation": 2, "vocab": "t_pin", "action": "adopt",
             "bucket": 640},
            {"after_evaluation": 4, "vocab": "t_pin", "action": "drop",
             "bucket": 640},
        ]

    def test_pinned_mode_with_no_pin_is_static(self):
        autotune.set_mode("pinned")
        for _ in range(5):
            assert autotune.CONTROLLER.evaluate() == []
        assert autotune.overlay() == {}

    def test_mode_zero_evaluates_nothing(self):
        autotune.set_mode("0")
        assert autotune.CONTROLLER.evaluate() == []
        assert autotune.CONTROLLER.evaluations == 0


# ------------------------------------------------------------- drop logic


class TestDropIdle:
    def test_idle_adopted_bucket_dropped_busy_op_only(self):
        _register("t_idle", (256, 1024), lambda nb: BUDGETED_SHA_KEY)
        autotune.set_mode("live")
        autotune._set_overlay("t_idle", (640,))
        # op quiet: no drop on thin evidence
        assert [d for d in autotune.CONTROLLER.evaluate()
                if d.get("action") == "drop"] == []
        assert autotune.overlay() == {"t_idle": (640,)}
        # op busy at OTHER buckets, zero hits at 640: dropped
        _feed_batches("t_idle", 256, 200, 12)
        drops = [d for d in autotune.CONTROLLER.evaluate()
                 if d.get("action") == "drop"]
        assert drops and drops[0]["bucket"] == 640
        assert autotune.overlay() == {}

    def test_live_bucket_with_traffic_survives(self):
        _register("t_live", (256, 1024), lambda nb: BUDGETED_SHA_KEY)
        autotune.set_mode("live")
        autotune._set_overlay("t_live", (640,))
        _feed_batches("t_live", 640, 500, 12)
        assert [d for d in autotune.CONTROLLER.evaluate()
                if d.get("action") == "drop"] == []
        assert autotune.overlay() == {"t_live": (640,)}


# ------------------------------------------------- measured backend cache


class TestMeasuredFqBackend:
    def test_measure_caches_and_auto_consults(self, tmp_path, monkeypatch):
        """The A/B measurement writes its winner per (device_kind, jax
        version) next to the compile cache, and fq's ``auto`` resolution
        prefers the measurement over the platform guess — asserted by
        caching int8 on this CPU host, where the guess would say int32."""
        from lighthouse_tpu.ops import fq

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = []

        def fake_probe(backend, rows=512, reps=3):
            calls.append(backend)
            return 0.010 if backend == "int8" else 0.025

        monkeypatch.setattr(fq, "measure_backend_seconds", fake_probe)
        autotune.set_mode("live")
        decision = autotune.measure_fq_backend(force=True)
        assert decision["backend"] == "int8"
        assert sorted(calls) == ["int32", "int8"]
        assert decision["measurements_s"]["int8"] < \
            decision["measurements_s"]["int32"]
        on_disk = json.loads(
            open(autotune.fq_backend_cache_path()).read())
        assert on_disk[autotune._fq_cache_key()]["backend"] == "int8"
        # second call reuses the cache — no probe re-run
        calls.clear()
        assert autotune.measure_fq_backend()["backend"] == "int8"
        assert calls == []
        # fq auto resolution: measurement beats the cpu->int32 guess
        monkeypatch.delenv(fq.FQ_BACKEND_ENV, raising=False)
        prev = fq.set_fq_backend(None)
        try:
            assert fq.active_fq_backend() == "int8"
        finally:
            fq.set_fq_backend(prev)
        # the decision is in the controller log / snapshot
        snap = autotune.snapshot()
        assert snap["fq_backend"]["backend"] == "int8"
        assert any(d["knob"] == "fq_backend" for d in snap["decisions"])

    def test_mode_zero_ignores_cache(self, tmp_path, monkeypatch):
        from lighthouse_tpu.ops import fq

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(fq, "measure_backend_seconds",
                            lambda backend, rows=512, reps=3: 0.01)
        autotune.set_mode("live")
        autotune.measure_fq_backend(force=True)
        autotune.set_mode("0")
        assert autotune.cached_fq_backend() is None
        monkeypatch.delenv(fq.FQ_BACKEND_ENV, raising=False)
        prev = fq.set_fq_backend(None)
        try:
            assert fq.active_fq_backend() == "int32"  # the plain guess
        finally:
            fq.set_fq_backend(prev)


# --------------------------------------------------- admission: the bounds


def _bulk_controller(adaptive=True, max_inflight=128, deadline_s=2.0,
                     retry_after_s=5):
    return AdmissionController(
        [ClassPolicy(CLASS_BULK, max_inflight=max_inflight,
                     deadline_s=deadline_s, retry_after_s=retry_after_s)],
        adaptive=adaptive)


class TestLatencyDrivenAdmission:
    def test_static_without_observations_or_adaptive(self):
        ctrl = _bulk_controller(adaptive=True)
        assert ctrl.effective_bounds(CLASS_BULK) == (128, 2.0)
        ctrl2 = _bulk_controller(adaptive=False)
        ctrl2._ewma[CLASS_BULK] = 0.5
        assert ctrl2.effective_bounds(CLASS_BULK) == (128, 2.0)

    def test_bounds_track_ewma_inside_the_band(self):
        ctrl = _bulk_controller(adaptive=True)
        # slow handlers (0.2 s): deadline 4x ewma = 0.8, inflight =
        # deadline/ewma = 4, floored at 128/8 = 16
        ctrl._ewma[CLASS_BULK] = 0.2
        assert ctrl.effective_bounds(CLASS_BULK) == (16, 0.8)
        # very slow (1.0 s): deadline hits the static ceiling, inflight
        # 2.0/1.0 = 2 -> floor 16
        ctrl._ewma[CLASS_BULK] = 1.0
        assert ctrl.effective_bounds(CLASS_BULK) == (16, 2.0)
        # fast handlers (1 ms): deadline hits the floor (static/4),
        # inflight back at the static ceiling
        ctrl._ewma[CLASS_BULK] = 0.001
        assert ctrl.effective_bounds(CLASS_BULK) == (128, 0.5)

    def test_ewma_converges_from_released_tickets(self):
        ctrl = _bulk_controller(adaptive=True)
        for _ in range(30):
            t = ctrl.try_admit(CLASS_BULK)
            t.check_deadline()
            t.started_pc -= 0.2  # the handler "took" 200 ms
            t.release()
        ewma = ctrl.snapshot()["latency_ewma_s"][CLASS_BULK]
        assert 0.15 < ewma < 0.25
        bound, deadline = ctrl.effective_bounds(CLASS_BULK)
        assert bound < 128 and deadline < 2.0

    def test_tightened_inflight_bound_sheds(self):
        ctrl = _bulk_controller(adaptive=True, max_inflight=16)
        ctrl._ewma[CLASS_BULK] = 0.2  # effective bound: max(2, 4) = 4
        bound, _ = ctrl.effective_bounds(CLASS_BULK)
        tickets = [ctrl.try_admit(CLASS_BULK) for _ in range(bound)]
        with pytest.raises(ShedError) as e:
            ctrl.try_admit(CLASS_BULK)
        assert e.value.reason == "admission_full"
        for t in tickets:
            t.release()

    def test_deadline_shed_uses_effective_deadline(self):
        """A request that would survive the static deadline is shed once
        the latency-tracked deadline tightened past its wait — and a shed
        ticket's queue wait must NOT feed the service-time EWMA."""
        ctrl = _bulk_controller(adaptive=True, deadline_s=5.0)
        ctrl._ewma[CLASS_BULK] = 0.01  # effective deadline: 5/4 = 1.25
        t = ctrl.try_admit(CLASS_BULK)
        t.admitted_pc -= 2.0  # waited 2 s in queue
        with pytest.raises(ShedError) as e:
            t.check_deadline()
        assert e.value.reason == "deadline"
        t.release()
        assert abs(ctrl._ewma[CLASS_BULK] - 0.01) < 1e-9

    def test_snapshot_reports_effective_bounds(self):
        ctrl = _bulk_controller(adaptive=True)
        ctrl._ewma[CLASS_BULK] = 0.2
        snap = ctrl.snapshot()
        assert snap["effective"][CLASS_BULK] == {
            "max_inflight": 16, "deadline_s": 0.8}
        assert snap["bounds"][CLASS_BULK] == 128  # statics still reported


# ------------------------------------------------ admission: Retry-After


class TestRetryAfterDrainRate:
    def test_falls_back_to_constant_below_sample_floor(self):
        ctrl = _bulk_controller(retry_after_s=7)
        assert ctrl.retry_after(CLASS_BULK) == 7
        # a few completions are still below the floor
        now = time.perf_counter()
        ctrl._done[CLASS_BULK].extend(now + i for i in range(4))
        assert ctrl.retry_after(CLASS_BULK) == 7

    def test_derived_from_observed_drain_rate(self):
        """16 completions 1 s apart = 1/s drain; 4 inflight -> half the
        backlog drains in 2 s -> Retry-After 2 (not the constant 7)."""
        ctrl = _bulk_controller(retry_after_s=7, adaptive=False)
        base = time.perf_counter()
        ctrl._done[CLASS_BULK].extend(base + i * 1.0 for i in range(16))
        tickets = [ctrl.try_admit(CLASS_BULK) for _ in range(4)]
        assert ctrl.retry_after(CLASS_BULK) == 2
        for t in tickets:
            t.release()

    def test_derived_value_rides_the_shed_response(self):
        ctrl = _bulk_controller(max_inflight=2, retry_after_s=7,
                                adaptive=False)
        base = time.perf_counter()
        ctrl._done[CLASS_BULK].extend(base + i * 1.0 for i in range(16))
        t1, t2 = ctrl.try_admit(CLASS_BULK), ctrl.try_admit(CLASS_BULK)
        with pytest.raises(ShedError) as e:
            ctrl.try_admit(CLASS_BULK)
        assert e.value.retry_after_s == 1  # ceil((2/2)/1.0) = 1, derived
        t1.release(), t2.release()

    def test_clamped_to_ceiling_when_drain_is_glacial(self):
        ctrl = _bulk_controller(retry_after_s=7)
        base = time.perf_counter()
        # 16 completions over 1600 s -> 0.01/s; backlog 8 -> 400 s, clamped
        ctrl._done[CLASS_BULK].extend(base + i * 100.0 for i in range(16))
        tickets = [ctrl.try_admit(CLASS_BULK) for _ in range(8)]
        assert ctrl.retry_after(CLASS_BULK) == 30
        for t in tickets:
            t.release()


# ----------------------------------------------------------- the real path


@pytest.mark.slow
def test_real_warmup_and_adoption_end_to_end():
    """The unmocked loop: flight-recorder evidence at the sha 1024 bucket
    -> densify candidate 640 -> committed-budget gate passes -> REAL AOT
    warmup (XLA compile / persistent-cache deserialize) -> adoption ->
    ``_bucket`` routes gap-sized layers to the new bucket."""
    from lighthouse_tpu.ops import sha256_device

    autotune.set_mode("live")
    _feed_batches("sha256_pairs", 1024, 300, 12)
    deadline = time.time() + 300
    adopted = False
    while time.time() < deadline:
        autotune.CONTROLLER.evaluate()
        if 640 in autotune.overlay().get("sha256_pairs", ()):
            adopted = True
            break
        time.sleep(0.5)
    assert adopted, autotune.CONTROLLER.decision_log()
    assert sha256_device._bucket(500) == 640
    # the warmup pre-seeded the compile mirror, so the first production
    # dispatch at 640 will not be misattributed as a compile
    assert any(e["op"] == "sha256_pairs" and e["shape"] == "640"
               for e in device_telemetry.COMPILE_CACHE.inventory())
