"""Self-tuning device runtime: the telemetry→knob control plane (ISSUE 15).

Every signal this module consumes already exists — the flight recorder's
per-batch occupancy (PR 4), the padding-waste percentiles, the admission
wait histograms (PR 14) — but until now only adaptive linger closed a loop
from any of them.  This controller closes three more, each decision
observable (``GET /lighthouse/autotune``, ``autotune_decisions_total``)
and pinnable:

1. **Live bucket vocabulary.**  The ``bucket_tuning.py`` heuristics run
   against the flight recorder at runtime: an effective bucket whose
   median dispatched batch fills under half its lanes earns a midpoint
   bucket (only where the vocabulary has a real >2x gap — a ratio-2
   vocabulary cannot waste more than half from bucket quantization).
   Adoption is guarded twice: the candidate must carry a committed
   ``hlo_budget_baseline.json`` entry (an unbudgeted shape would silently
   escape the static lowering gate — the controller refuses instead), and
   in live mode its compile cost must have been paid off-path through the
   AOT-warmup machinery (``ops/compile_cache.aot_warmup_op``) before the
   first production batch can land on it.  Adopted buckets overlay the
   static vocabularies (``ops/verify.py`` / ``ops/sha256_device.py`` /
   ``ops/epoch_device.py`` consult :func:`bucket_vocabulary`); the static
   tuples remain the floor and their top bucket the ceiling — the overlay
   never changes ``MAX_SETS_PER_DISPATCH`` semantics.

2. **Measured fq backend selection.**  ``LIGHTHOUSE_TPU_FQ_BACKEND=auto``
   used to be a platform guess (int8 on TPU, int32 elsewhere).
   :func:`measure_fq_backend` runs a short in-situ A/B microbench — one
   small operand batch through BOTH lowerings, supervised dispatch
   (``device_supervisor.run("autotune_probe", ...)`` so a hung device
   cannot stall startup) — and caches the winner per
   ``(device_kind, jax version)`` in the persistent compile-cache dir.
   ``ops/fq.active_fq_backend`` consults that cache before guessing.

3. **Latency-driven admission.**  Implemented in
   ``scheduler/admission.py`` against this module's mode: in live mode the
   per-class inflight bounds and dequeue deadlines track observed handler
   latency EWMAs inside a bounded band around the configured statics
   (which remain the floor/ceiling), and Retry-After always reflects the
   class's observed drain rate (constant fallback below the sample floor).

**Determinism by construction.**  ``LIGHTHOUSE_TPU_AUTOTUNE=0|pinned|live``
(default ``pinned``).  ``0`` disables everything — static behavior, zero
overhead.  ``pinned`` applies only decisions replayed from an installed
pin (a recorded decision list keyed by *evaluation index*, never
wall-clock — the scenario 2-run determinism gate is fragile to wall-clock
shifts, so the controller's clock inside scenarios is the evaluation
counter the runner drives once per slot); with no pin installed, pinned
mode is exactly static behavior.  ``live`` reads the telemetry.  A live
run's decisions export as a pin (:meth:`Controller.export_pin`), so a
tuned configuration replays bit-identically.

This module is HOST-side only: it reads telemetry rings and JSON files and
never materializes a device value — the host-sync and lock-order static
passes scan it (``scripts/analysis/{host_sync,lock_order}_pass.py``) and
must stay at zero findings.  The device-touching legs live where device
code belongs: the warmup in ``ops/compile_cache.py``, the A/B probe in
``ops/fq.py`` (both reached only from live-mode control actions).
"""

from __future__ import annotations

import json
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import blackbox, locksmith, metrics
from .logs import get_logger

log = get_logger("autotune")

ENV = "LIGHTHOUSE_TPU_AUTOTUNE"
PIN_ENV = "LIGHTHOUSE_TPU_AUTOTUNE_PIN"
INTERVAL_ENV = "LIGHTHOUSE_TPU_AUTOTUNE_INTERVAL_S"
MODES = ("0", "pinned", "live")

#: bucket_tuning.py's densify threshold, applied at runtime: a bucket whose
#: median dispatched batch fills under half its lanes is waste-dominated.
DENSIFY_BELOW = 0.5
#: Minimum dispatched batches at one bucket before its occupancy is
#: evidence (same floor as bucket_tuning.py).
MIN_SAMPLES = 8
#: An adopted bucket with zero hits over a full recorder window while its
#: op stayed busy (>= MIN_SAMPLES batches) has stopped earning its keep.
DROP_IDLE_MIN_OP_SAMPLES = MIN_SAMPLES

#: Decision-log ring bound (the artifact of record for pins/ scenarios).
MAX_DECISIONS = 256

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The committed StableHLO budget baseline — the adoption gate reads its
#: KEYS (an adopted bucket must already be a build-gated lowering).
BUDGET_BASELINE_PATH = os.path.join(
    _REPO_ROOT, "scripts", "analysis", "hlo_budget_baseline.json")

AUTOTUNE_EVALUATIONS = metrics.counter(
    "autotune_evaluations_total",
    "controller evaluation passes (live telemetry reads or pin replays)",
)
AUTOTUNE_DECISIONS = metrics.counter(
    "autotune_decisions_total",
    "controller decisions, by knob and outcome (adopted|dropped|"
    "refused_no_budget|warmup_started|warmup_pending|refused_warmup_failed|"
    "refused_above_top|refused_meshed|measured)",
)
AUTOTUNE_OVERLAY_BUCKETS = metrics.gauge(
    "autotune_overlay_buckets",
    "live bucket-vocabulary overlay size, by vocabulary",
)
AUTOTUNE_FQ_BACKEND = metrics.gauge(
    "autotune_fq_backend_selected",
    "measured fq-backend selection (1 = selected), by backend",
)
AUTOTUNE_FQ_MEASURE_SECONDS = metrics.histogram(
    "autotune_fq_backend_measure_seconds",
    "per-backend duration of the fq A/B microbench, by backend",
)


# ------------------------------------------------------------------- mode

_MODE: Optional[str] = None
_MODE_LOCK = locksmith.lock("autotune._MODE_LOCK")


def mode() -> str:
    """The controller mode, resolved lazily from ``LIGHTHOUSE_TPU_AUTOTUNE``
    (default ``pinned`` — with no pin installed that is exactly static
    behavior, so tests and scenarios see no wall-clock-driven change)."""
    global _MODE
    if _MODE is None:
        with _MODE_LOCK:
            if _MODE is None:
                raw = os.environ.get(ENV, "pinned").strip().lower() or "pinned"
                if raw not in MODES:
                    # resolved lazily from hot paths (admission bounds,
                    # /lighthouse/device) — a config typo must degrade to
                    # the do-nothing default with a loud log line, never
                    # 500 the serving surface at runtime
                    log.warning("invalid autotune mode, using 'pinned'",
                                env=ENV, value=raw, expected=list(MODES))
                    raw = "pinned"
                _MODE = raw
    return _MODE


def set_mode(new_mode: Optional[str]) -> Optional[str]:
    """Force the mode (tests/scenarios/bench) or reset to env (None).
    Returns the previous forced value."""
    global _MODE
    if new_mode is not None and new_mode not in MODES:
        raise ValueError(f"unknown autotune mode {new_mode!r}")
    with _MODE_LOCK:
        prev, _MODE = _MODE, new_mode
    _refresh_active()
    return prev


def enabled() -> bool:
    return mode() != "0"


def live() -> bool:
    return mode() == "live"


# ------------------------------------------------- bucket vocabulary overlay


class VocabSpec:
    """One tunable bucket vocabulary: its static tuple (the floor), the
    telemetry op names whose flight records evidence it, the committed-
    budget key for a candidate bucket, and the off-path warmup hook."""

    __slots__ = ("name", "static", "telemetry_ops", "budget_key", "warmup")

    def __init__(self, name: str, static: Sequence[int],
                 telemetry_ops: Sequence[str],
                 budget_key: Callable[[int], str],
                 warmup: Optional[Callable[[int], None]]):
        self.name = name
        self.static = tuple(int(b) for b in static)
        self.telemetry_ops = tuple(telemetry_ops)
        self.budget_key = budget_key
        self.warmup = warmup


#: vocabulary name -> VocabSpec; populated by the ops modules at import
#: time, so the controller only ever sees vocabularies that are actually
#: loaded in this process.  Survives reset_for_tests (it mirrors imports).
_VOCABS: Dict[str, VocabSpec] = {}

#: vocabulary name -> merged (static + adopted) tuple.  Copy-on-write: the
#: hot bucket_vocabulary() path reads it without the lock.
_MERGED: Dict[str, Tuple[int, ...]] = {}
_OVERLAY: Dict[str, Tuple[int, ...]] = {}
_OVERLAY_LOCK = locksmith.lock("autotune._OVERLAY_LOCK")

#: Fast-path flag: True iff the overlay is non-empty AND the mode allows
#: it — bucket_vocabulary() is on every device dispatch, so the off case
#: must cost one attribute read.
_ACTIVE = False


def register_vocabulary(name: str, static: Sequence[int], *,
                        telemetry_ops: Sequence[str],
                        budget_key: Callable[[int], str],
                        warmup: Optional[Callable[[int], None]] = None,
                        ) -> None:
    """Called by an ops module at import time to enroll its bucket
    vocabulary in the control plane.  Idempotent (re-imports keep the
    latest registration)."""
    _VOCABS[name] = VocabSpec(name, static, telemetry_ops, budget_key, warmup)


def _refresh_active() -> None:
    global _ACTIVE
    _ACTIVE = bool(_OVERLAY) and mode() != "0"


def bucket_vocabulary(name: str, static: Tuple[int, ...]) -> Tuple[int, ...]:
    """The vocabulary a dispatch should bucket against: the static tuple,
    merged with any adopted overlay buckets.  The off path (no overlay, or
    autotune disabled) returns ``static`` untouched."""
    if not _ACTIVE:
        return static
    merged = _MERGED.get(name)
    return merged if merged is not None else static


def overlay() -> Dict[str, Tuple[int, ...]]:
    with _OVERLAY_LOCK:
        return dict(_OVERLAY)


def _set_overlay(name: str, buckets: Tuple[int, ...]) -> None:
    """Replace one vocabulary's overlay (copy-on-write merge rebuild)."""
    spec = _VOCABS[name]
    with _OVERLAY_LOCK:
        if buckets:
            _OVERLAY[name] = tuple(sorted(buckets))
            _MERGED[name] = tuple(sorted(set(spec.static) | set(buckets)))
        else:
            _OVERLAY.pop(name, None)
            _MERGED.pop(name, None)
    AUTOTUNE_OVERLAY_BUCKETS.set(len(buckets), vocabulary=name)
    _refresh_active()


# -------------------------------------------------------------- budget gate

_BUDGET_CACHE: Tuple[Optional[float], frozenset] = (None, frozenset())
_BUDGET_LOCK = locksmith.lock("autotune._BUDGET_LOCK")


def budget_keys() -> frozenset:
    """The committed hlo_budget baseline keys (mtime-cached).  An empty set
    when the baseline is unreadable — then NOTHING can be adopted, which is
    the honest failure mode for a build gate."""
    global _BUDGET_CACHE
    try:
        mtime = os.path.getmtime(BUDGET_BASELINE_PATH)
    except OSError:
        return frozenset()
    with _BUDGET_LOCK:
        cached_mtime, keys = _BUDGET_CACHE
        if cached_mtime == mtime:
            return keys
        try:
            with open(BUDGET_BASELINE_PATH, "r", encoding="utf-8") as f:
                keys = frozenset(json.load(f))
        except (OSError, ValueError):
            keys = frozenset()
        _BUDGET_CACHE = (mtime, keys)
        return keys


# --------------------------------------------------------------- controller


class Controller:
    """The one decision-maker.  ``evaluate()`` is the clock: scenarios call
    it once per slot, the live background thread on an interval, bench
    loops explicitly — decisions key on the evaluation index, so a pinned
    replay is wall-clock-free by construction."""

    def __init__(self) -> None:
        self._lock = locksmith.lock("Controller._lock")
        self.evaluations = 0
        self._decisions: List[dict] = []
        self._decision_seq = 0
        self._pin: List[dict] = []
        self._pin_applied = 0
        self._pin_loaded_env = False
        #: (vocab, bucket) -> "pending" | "done" | "failed"
        self._warmups: Dict[Tuple[str, int], str] = {}
        #: last recorded outcome per (knob, vocab, action, bucket): a
        #: STANDING live-mode refusal (no committed budget, warmup still
        #: compiling) re-evaluates every tick — without dedup it would
        #: flood the bounded decision ring (the artifact of record) with
        #: identical entries and evict the real adopt/drop history.
        self._last_outcome: Dict[Tuple, str] = {}
        #: (vocab, bucket) -> flight-recorder recorded_total at adoption:
        #: a fresh adoption gets a full recorder window of evidence before
        #: the idle-drop heuristic may judge it (otherwise the drop fires
        #: in the same evaluation that adopted — zero hits yet, trivially)
        self._adopted_seq: Dict[Tuple[str, int], int] = {}
        self._fq_decision: Optional[dict] = None

    # ------------------------------------------------------------- records

    def _record(self, dedupe: bool = False, **fields) -> dict:
        entry = dict(fields)
        key = (entry.get("knob"), entry.get("vocab"), entry.get("action"),
               entry.get("bucket"))
        with self._lock:
            duplicate = (dedupe
                         and self._last_outcome.get(key) == entry.get("outcome"))
            if not duplicate:
                self._last_outcome[key] = entry.get("outcome")
                # a recorded adopt/drop resets the sibling action's memory,
                # so a genuine drop→re-adopt cycle records every leg
                if entry.get("action") in ("adopt", "drop"):
                    sibling = "drop" if entry["action"] == "adopt" else "adopt"
                    self._last_outcome.pop(
                        (entry.get("knob"), entry.get("vocab"), sibling,
                         entry.get("bucket")), None)
                self._decision_seq += 1
                entry["seq"] = self._decision_seq
                self._decisions.append(entry)
                if len(self._decisions) > MAX_DECISIONS:
                    self._decisions = self._decisions[-MAX_DECISIONS:]
        AUTOTUNE_DECISIONS.inc(knob=entry.get("knob", "?"),
                               outcome=entry.get("outcome", "?"))
        if duplicate:
            # a standing decision re-reached on a later evaluation: counted
            # on the metric, not re-appended to the ring
            return entry
        log.info("autotune decision", **{
            k: v for k, v in entry.items() if k != "measurements_s"})
        blackbox.emit("autotune", "decision", knob=entry.get("knob"),
                      action=entry.get("action"), outcome=entry.get("outcome"),
                      vocab=entry.get("vocab"), bucket=entry.get("bucket"),
                      decision_seq=entry.get("seq"))
        return entry

    def decision_log(self) -> List[dict]:
        with self._lock:
            return [dict(d) for d in self._decisions]

    def export_pin(self) -> List[dict]:
        """The applied bucket decisions as a replayable pin: adopt/drop
        actions with their evaluation indices.  Feed the result to
        :meth:`install_pin` (or ``LIGHTHOUSE_TPU_AUTOTUNE_PIN``) and a
        pinned run replays the same vocabulary trajectory with no
        telemetry and no wall-clock."""
        out = []
        for d in self.decision_log():
            if d.get("knob") == "bucket" and d.get("outcome") in (
                    "adopted", "dropped"):
                out.append({
                    "after_evaluation": d["evaluation"],
                    "vocab": d["vocab"],
                    "action": "adopt" if d["outcome"] == "adopted" else "drop",
                    "bucket": d["bucket"],
                })
        return out

    def install_pin(self, decisions: Sequence[dict]) -> None:
        """Install a pinned decision list (sorted by evaluation index).
        Only consulted in ``pinned`` mode."""
        pin = sorted((dict(d) for d in decisions),
                     key=lambda d: int(d.get("after_evaluation", 0)))
        with self._lock:
            self._pin = pin
            self._pin_applied = 0

    def _maybe_load_env_pin(self) -> None:
        with self._lock:
            if self._pin_loaded_env or self._pin:
                return
            self._pin_loaded_env = True
        path = os.environ.get(PIN_ENV, "").strip()
        if not path:
            return
        try:
            with open(path, "r", encoding="utf-8") as f:
                self.install_pin(json.load(f))
            log.info("autotune pin loaded", path=path)
        except (OSError, ValueError) as e:
            log.warning("autotune pin unreadable", path=path, error=str(e))

    # ------------------------------------------------------------ evaluate

    def evaluate(self) -> List[dict]:
        """One control pass.  Live: read the flight recorder, walk the
        densify/drop heuristics through the guardrails.  Pinned: apply the
        pin entries whose evaluation index has arrived.  Off: nothing."""
        m = mode()
        if m == "0":
            return []
        with self._lock:
            self.evaluations += 1
            n = self.evaluations
        AUTOTUNE_EVALUATIONS.inc()
        if m == "pinned":
            self._maybe_load_env_pin()
            return self._apply_pin(n)
        return self._evaluate_live(n)

    # --- pinned replay

    def _apply_pin(self, evaluation: int) -> List[dict]:
        applied: List[dict] = []
        while True:
            with self._lock:
                if self._pin_applied >= len(self._pin):
                    return applied
                entry = self._pin[self._pin_applied]
                if int(entry.get("after_evaluation", 0)) > evaluation:
                    return applied
                self._pin_applied += 1
            applied.append(self._apply_pinned_entry(entry, evaluation))

    def _apply_pinned_entry(self, entry: dict, evaluation: int) -> dict:
        name = entry.get("vocab")
        action = entry.get("action")
        bucket = int(entry.get("bucket", 0))
        spec = _VOCABS.get(name)
        if spec is None:
            return self._record(knob="bucket", vocab=name, action=action,
                                bucket=bucket, evaluation=evaluation,
                                via="pin", outcome="refused_unknown_vocab",
                                reason=f"no registered vocabulary {name!r}")
        if action == "drop":
            current = set(overlay().get(name, ()))
            current.discard(bucket)
            _set_overlay(name, tuple(current))
            return self._record(knob="bucket", vocab=name, action="drop",
                                bucket=bucket, evaluation=evaluation,
                                via="pin", outcome="dropped",
                                reason="pinned replay")
        # adopt: the committed-budget gate holds even for a replay — a pin
        # must never smuggle an unbudgeted lowering past the static gate.
        # The warmup gate does NOT apply: the pin replays a run whose
        # compile cost was already paid (wall-clock must not re-enter).
        refused = self._refuse_adopt(spec, bucket, require_warmup=False)
        if refused is not None:
            return self._record(knob="bucket", vocab=name, action="adopt",
                                bucket=bucket, evaluation=evaluation,
                                via="pin", **refused)
        self._adopt(spec, bucket)
        return self._record(knob="bucket", vocab=name, action="adopt",
                            bucket=bucket, evaluation=evaluation,
                            via="pin", outcome="adopted",
                            reason="pinned replay (budget gate held)")

    # --- live telemetry

    def _evaluate_live(self, evaluation: int) -> List[dict]:
        decisions: List[dict] = []
        for name, spec in sorted(_VOCABS.items()):
            stats = _bucket_live_stats(spec)
            effective = bucket_vocabulary(name, spec.static)
            decisions.extend(
                self._densify(spec, effective, stats, evaluation))
            decisions.extend(
                self._drop_idle(spec, stats, evaluation))
        return decisions

    def _densify(self, spec: VocabSpec, effective: Tuple[int, ...],
                 stats: Dict[int, List[int]], evaluation: int) -> List[dict]:
        out: List[dict] = []
        for i, nb in enumerate(effective):
            live = stats.get(nb, ())
            if len(live) < MIN_SAMPLES:
                continue
            ordered = sorted(live)
            p50 = ordered[len(ordered) // 2] / nb
            if p50 >= DENSIFY_BELOW:
                continue
            prev = effective[i - 1] if i else 0
            if prev <= 0 or nb <= 2 * prev:
                # ratio-2 dense below this bucket: quantization cannot
                # waste more than half — the low p50 is a traffic question
                # (linger/coalescing), not a vocabulary one.
                continue
            mid = (prev + nb) // 2
            if mid in effective:
                continue
            out.append(self._try_adopt(
                spec, mid, evaluation,
                reason=(f"bucket {nb}: p50 occupancy {p50:.2f} < "
                        f"{DENSIFY_BELOW} over {len(live)} batches — "
                        f"midpoint {mid} bounds quantization waste at "
                        "~50%")))
        return out

    def _drop_idle(self, spec: VocabSpec, stats: Dict[int, List[int]],
                   evaluation: int) -> List[dict]:
        adopted = overlay().get(spec.name, ())
        if not adopted:
            return []
        op_samples = sum(len(v) for v in stats.values())
        if op_samples < DROP_IDLE_MIN_OP_SAMPLES:
            return []
        from . import device_telemetry

        recorded = device_telemetry.FLIGHT_RECORDER.recorded_total
        window = device_telemetry.FLIGHT_RECORDER.capacity
        out: List[dict] = []
        for bucket in adopted:
            if stats.get(bucket):
                continue
            seq = self._adopted_seq.get((spec.name, bucket))
            if seq is not None and recorded - seq < window:
                continue  # adopted inside the current evidence window
            current = set(overlay().get(spec.name, ()))
            current.discard(bucket)
            _set_overlay(spec.name, tuple(current))
            out.append(self._record(
                knob="bucket", vocab=spec.name, action="drop",
                bucket=bucket, evaluation=evaluation, via="live",
                outcome="dropped",
                reason=(f"zero dispatches at {bucket} across the last "
                        f"{op_samples} recorded batches — the traffic "
                        "that earned it has moved")))
        return out

    def _refuse_adopt(self, spec: VocabSpec, bucket: int,
                      require_warmup: bool) -> Optional[dict]:
        """The guardrails, in order.  Returns outcome/reason fields when
        the adoption must be refused (or deferred), None when it may
        proceed."""
        if bucket >= spec.static[-1]:
            return {"outcome": "refused_above_top",
                    "reason": (f"{bucket} >= static top {spec.static[-1]} — "
                               "the top bucket bounds chunking semantics "
                               "and stays a reviewed-diff decision")}
        from . import device_mesh

        if device_mesh.enabled():
            # A meshed dispatch at the new bucket would compile a DISTINCT
            # sharded executable (e.g. 640@dp8) that neither the warmup
            # nor the budget baseline covers — on-path compile through an
            # unaudited lowering.  Mesh-aware adoption (per-topology
            # warmup + |dpN| budget keys) is the TPU round's work
            # (ROADMAP item 2); until then the controller refuses.
            return {"outcome": "refused_meshed",
                    "reason": (f"device mesh is enabled (size "
                               f"{device_mesh.size()}): adoption would "
                               "compile an unwarmed, unbudgeted sharded "
                               "executable on-path — mesh-aware adoption "
                               "is ROADMAP item 2's hardware round")}
        if bucket in bucket_vocabulary(spec.name, spec.static):
            return {"outcome": "noop", "reason": "already in the vocabulary"}
        # budget_key may name several keys (the epoch vocabulary compiles
        # one lowering per leak mode) — every one must be committed.
        keys = spec.budget_key(bucket)
        if isinstance(keys, str):
            keys = (keys,)
        committed = budget_keys()
        missing = [k for k in keys if k not in committed]
        if missing:
            return {"outcome": "refused_no_budget",
                    "reason": (f"no committed hlo_budget entry {missing!r} — "
                               "adopting would route production batches "
                               "through a lowering the static gate never "
                               "audited; commit the budget first "
                               "(scripts/analysis/hlo_budget.py)")}
        if not require_warmup:
            return None
        with self._lock:
            state = self._warmups.get((spec.name, bucket))
        if state == "done":
            return None
        if state == "failed":
            return {"outcome": "refused_warmup_failed",
                    "reason": "off-path AOT warmup failed — see logs"}
        if state == "pending":
            return {"outcome": "warmup_pending",
                    "reason": "off-path AOT warmup still compiling"}
        if spec.warmup is None:
            return {"outcome": "refused_warmup_failed",
                    "reason": "vocabulary registered no warmup hook"}
        self._start_warmup(spec, bucket)
        return {"outcome": "warmup_started",
                "reason": ("compile cost must be paid off-path before the "
                           "first production batch lands on the bucket — "
                           "AOT warmup kicked on a background thread")}

    def _try_adopt(self, spec: VocabSpec, bucket: int, evaluation: int,
                   reason: str) -> dict:
        refused = self._refuse_adopt(spec, bucket, require_warmup=True)
        if refused is not None:
            # dedupe: a standing refusal re-reached every evaluation must
            # not flood the bounded ring (it records once per outcome)
            return self._record(dedupe=True, knob="bucket", vocab=spec.name,
                                action="adopt", bucket=bucket,
                                evaluation=evaluation, via="live",
                                trigger=reason, **refused)
        self._adopt(spec, bucket)
        return self._record(knob="bucket", vocab=spec.name, action="adopt",
                            bucket=bucket, evaluation=evaluation, via="live",
                            outcome="adopted", reason=reason)

    def _adopt(self, spec: VocabSpec, bucket: int) -> None:
        from . import device_telemetry

        current = set(overlay().get(spec.name, ()))
        current.add(bucket)
        _set_overlay(spec.name, tuple(current))
        self._adopted_seq[(spec.name, bucket)] = \
            device_telemetry.FLIGHT_RECORDER.recorded_total

    def _start_warmup(self, spec: VocabSpec, bucket: int) -> None:
        key = (spec.name, bucket)
        with self._lock:
            self._warmups[key] = "pending"

        def work() -> None:
            try:
                spec.warmup(bucket)
            except Exception:
                log.warning("autotune warmup failed", vocab=spec.name,
                            bucket=bucket, exc_info=True)
                self._finish_warmup(key, "failed")
            else:
                self._finish_warmup(key, "done")

        threading.Thread(
            target=work, daemon=True,
            name=f"autotune-warm-{spec.name}-{bucket}").start()

    def _finish_warmup(self, key: Tuple[str, int], state: str) -> None:
        """Completion callback, locked — and generation-safe: a compile
        thread finishing AFTER a reset (scenario cleanup, tests) finds its
        'pending' entry gone and must NOT resurrect a stale done/failed
        state into the fresh controller."""
        with self._lock:
            if self._warmups.get(key) == "pending":
                self._warmups[key] = state

    # ------------------------------------------------------------ snapshot

    def snapshot(self) -> dict:
        with self._lock:
            pin = [dict(d) for d in self._pin]
            pin_applied = self._pin_applied
            evaluations = self.evaluations
            warmups = {f"{k[0]}:{k[1]}": v for k, v in self._warmups.items()}
            fq = dict(self._fq_decision) if self._fq_decision else None
        return {
            "mode": mode(),
            "evaluations": evaluations,
            "vocabularies": {
                name: {
                    "static": list(spec.static),
                    "overlay": list(overlay().get(name, ())),
                    "effective": list(
                        bucket_vocabulary(name, spec.static)),
                }
                for name, spec in sorted(_VOCABS.items())
            },
            "warmups": warmups,
            "decisions": self.decision_log(),
            "pin": {"installed": len(pin), "applied": pin_applied,
                    "entries": pin},
            "fq_backend": fq or cached_fq_backend(),
        }

    def reset(self) -> None:
        with self._lock:
            self.evaluations = 0
            self._decisions = []
            self._decision_seq = 0
            self._pin = []
            self._pin_applied = 0
            self._pin_loaded_env = False
            self._warmups = {}
            self._adopted_seq = {}
            self._last_outcome = {}
            self._fq_decision = None


CONTROLLER = Controller()


def _bucket_live_stats(spec: VocabSpec) -> Dict[int, List[int]]:
    """bucket size -> live sizes of the dispatched batches that ran at it,
    over the flight-recorder window of the spec's telemetry ops.  Records
    the breaker routed to the host never dispatched and stay out (their
    ``occupancy_sets`` is absent — same rule the padding-waste metrics
    follow)."""
    from . import device_telemetry

    stats: Dict[int, List[int]] = {}
    for op in spec.telemetry_ops:
        for r in device_telemetry.FLIGHT_RECORDER.recent(
                limit=device_telemetry.FLIGHT_RECORDER.capacity, op=op):
            if "occupancy_sets" not in r:
                continue
            shape = str(r.get("shape", ""))
            try:
                nb = int(shape.split("@")[0].split("x")[0])
            except ValueError:
                continue
            stats.setdefault(nb, []).append(int(r.get("n_live", 0)))
    return stats


# ----------------------------------------------- measured backend selection


def fq_backend_cache_path() -> str:
    """The decision cache rides in the persistent compile-cache dir — the
    same lifetime as the compiled programs the decision shapes."""
    from .ops.compile_cache import default_cache_dir

    return os.path.join(default_cache_dir(), "autotune_fq_backend.json")


_FQ_KEY: Optional[str] = None


def _fq_cache_key() -> str:
    """(device_kind, jax version) — TOUCHES jax (``jax.devices()``
    initializes the backend), so callers on host-only paths must use the
    memoized value via ``cached_fq_backend(compute_key=False)``."""
    global _FQ_KEY
    if _FQ_KEY is None:
        import jax

        d = jax.devices()[0]
        kind = getattr(d, "device_kind", "") or d.platform
        _FQ_KEY = f"{kind}|jax-{jax.__version__}"
    return _FQ_KEY


def cached_fq_backend(compute_key: bool = False) -> Optional[dict]:
    """The cached measured decision for THIS (device_kind, jax version),
    or None (no measurement yet / cache unreadable / autotune off).

    ``compute_key=True`` may initialize jax to derive the cache key —
    only the fq ``auto`` resolution passes it (that path queries the jax
    platform right after anyway).  The default reuses the memoized key,
    so host-side surfaces (``/lighthouse/autotune``, check_metrics'
    import) never initialize a device backend."""
    if not enabled():
        return None
    key = None
    if compute_key:
        try:
            key = _fq_cache_key()
        except Exception:
            return None
    else:
        key = _FQ_KEY
    if key is None:
        return None
    try:
        with open(fq_backend_cache_path(), "r", encoding="utf-8") as f:
            doc = json.load(f)
        entry = doc.get(key)
    except Exception:
        return None
    if not isinstance(entry, dict) or entry.get("backend") not in (
            "int8", "int32"):
        return None
    return entry


def measure_fq_backend(force: bool = False, rows: int = 512,
                       reps: int = 3) -> dict:
    """Run (or reuse) the in-situ fq-backend A/B microbench.

    Both lowerings run the same small operand batch through a supervised
    dispatch (op ``autotune_probe`` — watchdogged, so a hung device cannot
    stall node startup past the deadline); the winner is cached per
    ``(device_kind, jax version)`` next to the persistent compile cache
    and consulted by ``ops/fq.active_fq_backend`` in place of the old
    platform guess.  Raises on device failure — the caller falls back to
    the guess."""
    if not force:
        cached = cached_fq_backend()
        if cached is not None:
            return cached
    from . import device_supervisor
    from .ops import fq

    key = _fq_cache_key()
    measurements: Dict[str, float] = {}
    for backend in ("int32", "int8"):
        seconds = device_supervisor.run(
            "autotune_probe",
            lambda b=backend: fq.measure_backend_seconds(
                b, rows=rows, reps=reps),
        )
        measurements[backend] = round(float(seconds), 6)
        AUTOTUNE_FQ_MEASURE_SECONDS.observe(seconds, backend=backend)
    winner = min(measurements, key=measurements.get)
    decision = {
        "backend": winner,
        "measurements_s": measurements,
        "source": "measured",
        "key": key,
        "rows": rows,
        "reps": reps,
    }
    for backend in ("int32", "int8"):
        AUTOTUNE_FQ_BACKEND.set(1.0 if backend == winner else 0.0,
                                backend=backend)
    _write_fq_cache(key, decision)
    with CONTROLLER._lock:
        CONTROLLER._fq_decision = decision
    CONTROLLER._record(knob="fq_backend", action="select", backend=winner,
                       outcome="measured", measurements_s=measurements,
                       reason=f"A/B microbench at rows={rows} (best of "
                              f"{reps} supervised dispatches per backend)")
    return decision


def _write_fq_cache(key: str, decision: dict) -> None:
    path = fq_backend_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except Exception:
            doc = {}
        doc[key] = decision
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        log.warning("fq backend decision cache not written", path=path)


# ----------------------------------------------------------- startup hook

_THREAD: Optional[threading.Thread] = None
_THREAD_STOP: Optional[threading.Event] = None
_THREAD_LOCK = locksmith.lock("autotune._THREAD_LOCK")


def maybe_start_from_env() -> Optional[threading.Thread]:
    """Node-startup hook (``ClientBuilder.build`` for jax nodes): in live
    mode, run the measured backend selection (``FQ_BACKEND`` unset/auto
    only; cached across restarts) and start the periodic controller
    thread.  Pinned/off modes start nothing — scenario and test processes
    stay free of wall-clock control loops."""
    global _THREAD, _THREAD_STOP
    if not live():
        return None
    interval = float(os.environ.get(INTERVAL_ENV, "30"))
    with _THREAD_LOCK:
        if (_THREAD is not None and _THREAD.is_alive()
                and _THREAD_STOP is not None and not _THREAD_STOP.is_set()):
            return _THREAD
        # each controller thread owns its OWN stop event: a stop() racing
        # a restart can only kill the thread it targeted, never strand the
        # fresh one against a stale still-set global flag
        stop_event = threading.Event()

        def loop() -> None:
            from .ops.fq import FQ_BACKEND_ENV

            if os.environ.get(FQ_BACKEND_ENV, "auto").strip().lower() in (
                    "", "auto"):
                try:
                    decision = measure_fq_backend()
                except Exception:
                    log.warning("fq backend measurement failed; the "
                                "platform guess stands", exc_info=True)
                else:
                    # apply the winner to THIS process: traces cut after
                    # this point use the measured lowering.  Shapes that
                    # traced during the probe window keep the guess's
                    # lowering until restart (jax's trace cache) — the
                    # cached decision makes the restart right from the
                    # first trace.
                    from .ops import fq

                    fq.set_fq_backend(decision["backend"])
                    log.info("measured fq backend applied",
                             backend=decision["backend"])
            while not stop_event.wait(interval):
                try:
                    CONTROLLER.evaluate()
                except Exception:
                    log.warning("autotune evaluation failed", exc_info=True)

        _THREAD_STOP = stop_event
        _THREAD = threading.Thread(target=loop, daemon=True, name="autotune")
        _THREAD.start()
        return _THREAD


def stop() -> None:
    with _THREAD_LOCK:
        if _THREAD_STOP is not None:
            _THREAD_STOP.set()


def snapshot() -> dict:
    return CONTROLLER.snapshot()


def reset_for_tests() -> None:
    """Clear controller state, overlay, and forced mode (registrations
    persist — they mirror module imports)."""
    stop()
    CONTROLLER.reset()
    with _OVERLAY_LOCK:
        _OVERLAY.clear()
        _MERGED.clear()
    set_mode(None)
