"""Device-layer telemetry: compile-cache inventory, padding-waste accounting,
a batch flight recorder, and the on-demand profiler hook.

PR 2 gave the *host-side* pipeline span tracing — we can see that
``device_batch_wait`` was slow, not *why*.  The three usual suspects on an
accelerator are invisible without dedicated accounting:

- **cold XLA compiles** — the bucketed jit entry points (``ops/verify.py``,
  ``ops/epoch_device.py``, ``ops/sha256_device.py``) compile one executable
  per static shape; a first-seen ``(n_bucket, k_bucket)`` pays seconds of
  trace+compile inside what the histograms record as "dispatch".  Each
  entry point reports its dispatch through :func:`note_dispatch`, which
  keeps a host-side mirror of the jit cache (op, shape) → inventory entry,
  increments ``device_program_compiles_total{op,shape}`` exactly once per
  shape, and feeds ``device_program_compile_seconds`` on the compiling call.
- **padding waste** — batches are padded up to bucket shapes; a 33-set
  batch in a 64-bucket wastes half the device.  :func:`record_batch`
  accounts ``live/nb`` occupancy into ``device_batch_occupancy_ratio``
  histograms plus wasted-lane counters, making ``K_BUCKETS``/``N_BUCKETS``
  tuning data-driven.
- **device memory pressure** — :func:`device_memory_stats` samples
  ``device.memory_stats()`` per device; a registered collector mirrors the
  figures onto ``device_memory_bytes{device,stat}`` gauges on every scrape.

Every dispatched batch also lands in the bounded :class:`FlightRecorder`
ring (op, bucket shape, live sizes, per-stage durations, occupancy,
verdict, host-fallback flag, **trace id**), served by
``GET /lighthouse/device`` (summary) and ``GET /lighthouse/device/batches``.
The trace id links each record to its PR 2 span tree, so
``/lighthouse/traces/{id}`` and ``/lighthouse/device/batches``
cross-reference in both directions (the trace carries ``flight_seq``).

``POST /lighthouse/device/profile?seconds=N`` wraps ``jax.profiler.trace``
via :func:`capture_profile` for a Perfetto-loadable device dump (a clean
501 on CPU, where the device tracer has nothing to say).

Everything here is HOST-side bookkeeping called strictly outside the jit
boundary — the device-purity pass (``scripts/analysis/device_purity_pass``)
stays at zero findings by construction.
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from . import blackbox, metrics, tracing

#: Flight-recorder ring size.  ``LIGHTHOUSE_TPU_FLIGHT_RING`` is the
#: knob (long soaks size it up so pre-incident records survive to the
#: postmortem bundle); the older ``_FLIGHT_RECORDER_CAPACITY`` name is
#: honored as a fallback.
FLIGHT_RECORDER_CAPACITY = int(
    os.environ.get(
        "LIGHTHOUSE_TPU_FLIGHT_RING",
        os.environ.get("LIGHTHOUSE_TPU_FLIGHT_RECORDER_CAPACITY", "256"),
    )
)

#: Hard cap on one profiler capture — the HTTP task spawner allows 30 s per
#: handler, and the capture sleeps for its whole window.
MAX_PROFILE_SECONDS = 10.0


def _shape_label(shape: Tuple[int, ...], mesh: int = 0) -> str:
    label = "x".join(str(int(s)) for s in shape)
    # Sharded programs are distinct executables at the same bucket shape:
    # the mesh size is part of the identity ("128x32@dp8").
    return f"{label}@dp{int(mesh)}" if mesh else label


def active_trace_id() -> Optional[str]:
    """Trace id of the active span's trace (None outside any trace)."""
    sp = tracing.current_span()
    return sp.trace.trace_id if sp is not None else None


# ------------------------------------------------------- compile-cache mirror


class CompileCache:
    """Host-side mirror of the jit executable caches.

    jax caches one executable per (function, static shape); this mirror keys
    the same way — ``(op, shape)`` — so "first seen here" == "compiled
    there" for the bucketed entry points, whose dtypes are fixed.  The
    compiling call's dispatch duration approximates trace+compile time
    (subsequent dispatches of the same shape are sub-millisecond enqueues).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: Dict[Tuple[str, Tuple[int, ...]], dict] = {}

    def note_dispatch(self, op: str, shape: Tuple[int, ...], seconds: float,
                      mesh: int = 0) -> bool:
        """Record one dispatch of ``op`` at ``shape``; True iff first seen
        (the compiling call).  ``mesh`` > 0 marks a sharded executable —
        the same bucket shape compiles separately per mesh topology."""
        shape = tuple(int(s) for s in shape)
        mesh = int(mesh)
        now = time.time()
        with self._lock:
            entry = self._programs.get((op, shape, mesh))
            if entry is not None:
                entry["invocations"] += 1
                entry["last_used_ms"] = int(now * 1000)
                return False
            self._programs[(op, shape, mesh)] = {
                "op": op,
                "shape": _shape_label(shape, mesh),
                "compile_seconds": round(seconds, 4),
                "invocations": 1,
                "first_seen_ms": int(now * 1000),
                "last_used_ms": int(now * 1000),
            }
        metrics.DEVICE_PROGRAM_COMPILES.inc(op=op, shape=_shape_label(shape, mesh))
        metrics.DEVICE_PROGRAM_COMPILE_SECONDS.observe(seconds, op=op)
        return True

    def note_warmup(self, op: str, shape: Tuple[int, ...], seconds: float,
                    hit: bool) -> None:
        """Record an ahead-of-time warmup of ``(op, shape)`` (compile_cache
        ``warmup_standard_buckets``).  Pre-seeds the mirror so the shape's
        first production dispatch is NOT misattributed as a compile, and
        keeps the compiles counter honest: only a warmup MISS (a real XLA
        compile, vs a persistent-cache deserialize) increments
        ``device_program_compiles_total``."""
        shape = tuple(int(s) for s in shape)
        now = time.time()
        with self._lock:
            entry = self._programs.get((op, shape, 0))
            # A production dispatch can race the background warmup compile
            # for the same shape; if it won, note_dispatch already counted
            # the compile — the warmup must not count it a second time.
            already_counted = entry is not None
            if entry is None:
                entry = self._programs[(op, shape, 0)] = {
                    "op": op,
                    "shape": _shape_label(shape),
                    "compile_seconds": round(seconds, 4),
                    "invocations": 0,
                    "first_seen_ms": int(now * 1000),
                    "last_used_ms": int(now * 1000),
                }
            entry["source"] = "warmup"
            entry["warmup_outcome"] = "hit" if hit else "miss"
        outcome = "hit" if hit else "miss"
        metrics.DEVICE_AOT_WARMUP.inc(op=op, shape=_shape_label(shape),
                                      outcome=outcome)
        metrics.DEVICE_AOT_WARMUP_SECONDS.observe(seconds, op=op)
        if not hit and not already_counted:
            metrics.DEVICE_PROGRAM_COMPILES.inc(op=op, shape=_shape_label(shape))
            metrics.DEVICE_PROGRAM_COMPILE_SECONDS.observe(seconds, op=op)

    def seen(self, op: str, shape: Tuple[int, ...], mesh: int = 0) -> bool:
        """True iff (op, shape, mesh) already has a cached executable —
        i.e. the next dispatch will NOT compile.  Lets fault-injection
        sites target ``device.compile`` deterministically."""
        with self._lock:
            return (op, tuple(int(s) for s in shape), int(mesh)) in self._programs

    def invalidate_meshed(self) -> None:
        """Drop every sharded program's mirror entry (device_mesh reshard:
        the old topology's executables — AOT-warmed or production-compiled
        — are unreachable; the survivors' first dispatches must count as
        the compiles they are)."""
        with self._lock:
            self._programs = {
                k: v for k, v in self._programs.items() if k[2] == 0
            }

    def inventory(self) -> List[dict]:
        with self._lock:
            return sorted(
                (dict(e) for e in self._programs.values()),
                key=lambda e: (e["op"], e["shape"]),
            )

    def clear(self) -> None:
        """Reset the MIRROR only (tests) — jax's own cache is untouched, so
        a cleared mirror over-counts 'compiles' until shapes re-register."""
        with self._lock:
            self._programs.clear()


COMPILE_CACHE = CompileCache()


def note_dispatch(op: str, shape: Tuple[int, ...], seconds: float,
                  mesh: int = 0) -> bool:
    return COMPILE_CACHE.note_dispatch(op, shape, seconds, mesh=mesh)


def note_warmup(op: str, shape: Tuple[int, ...], seconds: float, hit: bool) -> None:
    COMPILE_CACHE.note_warmup(op, shape, seconds, hit)


# ------------------------------------------------------------ flight recorder


class FlightRecorder:
    """Bounded ring of the last N device-batch records."""

    def __init__(self, capacity: int = FLIGHT_RECORDER_CAPACITY):
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._seq = 0

    def record(self, entry: dict) -> dict:
        with self._lock:
            self._seq += 1
            entry["seq"] = self._seq
            self._ring.append(entry)
        return entry

    def recent(self, limit: int = 64, op: Optional[str] = None,
               trace_id: Optional[str] = None,
               node: Optional[str] = None) -> List[dict]:
        """Newest-first records, optionally filtered by op / trace id /
        originating node (fleet runs stamp ``node`` via telemetry_scope)."""
        with self._lock:
            records = list(self._ring)
        records.reverse()
        if op is not None:
            records = [r for r in records if r.get("op") == op]
        if trace_id is not None:
            records = [r for r in records if r.get("trace_id") == trace_id]
        if node is not None:
            records = [r for r in records if r.get("node") == node]
        return [dict(r) for r in records[:max(1, limit)]]

    @property
    def recorded_total(self) -> int:
        with self._lock:
            return self._seq

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


FLIGHT_RECORDER = FlightRecorder()

# Host-fallback tally by reason (also on the Prometheus counter; kept here
# so the /lighthouse/device summary needs no registry introspection).
_FALLBACKS: Dict[str, int] = {}
# process-boundary: ok(scope seam: per-node views live in telemetry_scope)
_FALLBACKS_LOCK = threading.Lock()


def record_batch(
    *,
    op: str,
    shape: Tuple[int, ...],
    n_live: int,
    live_keys: Optional[int] = None,
    key_rows: Optional[int] = None,
    n_groups: Optional[int] = None,
    work_mix: Optional[Dict[str, int]] = None,
    stages: Optional[Dict[str, float]] = None,
    verdict: Optional[bool] = None,
    host_fallback: bool = False,
    fallback_reason: Optional[str] = None,
    trace_id: Optional[str] = None,
    compiled: bool = False,
    breaker_state: Optional[str] = None,
    dispatched: bool = True,
    mesh: int = 0,
    shard_live: Optional[List[int]] = None,
) -> dict:
    """Account one dispatched device batch: occupancy histograms +
    wasted-lane counters + a flight-recorder entry.  Returns the entry
    (with its ``seq``) so callers can stamp the linkage on their span.
    ``mesh`` > 0 marks a sharded dispatch; ``shard_live`` is the per-shard
    live-row split (the per-shard occupancy view — bucket+mesh padding
    lands on the last shards, and this is where that shows).  ``key_rows``
    is the key axis's row count where it differs from ``shape[0]`` (sets
    spanning rows); the record's ``key_slots`` are its rows times
    ``shape[1]``."""
    shape = tuple(int(s) for s in shape)
    nb = shape[0]
    entry: Dict[str, Any] = {
        "t_ms": int(time.time() * 1000),
        "op": op,
        "shape": _shape_label(shape, mesh),
        "n_live": int(n_live),
        "compiled": bool(compiled),
        "host_fallback": bool(host_fallback),
        "trace_id": trace_id,
    }
    if mesh:
        entry["mesh"] = int(mesh)
    if n_groups is not None:
        # Pipeline-coalesced batches: how many caller groups rode this one
        # dispatch, and which work kinds contributed how many sets.
        entry["n_groups"] = int(n_groups)
    if work_mix:
        entry["work_mix"] = {str(k): int(v) for k, v in work_mix.items()}
    if stages:
        entry["stages_s"] = {k: round(float(v), 6) for k, v in stages.items()}
    if verdict is not None:
        entry["verdict"] = bool(verdict)
    if fallback_reason is not None:
        entry["fallback_reason"] = fallback_reason
    if breaker_state is not None:
        entry["breaker_state"] = breaker_state

    if dispatched and nb > 0:
        # A batch the breaker routed to the host never reached the device:
        # it is still flight-recorded, but stays out of the occupancy /
        # wasted-lane data that tunes K_BUCKETS/N_BUCKETS (no lanes were
        # actually dispatched).
        set_ratio = min(1.0, n_live / nb)
        entry["occupancy_sets"] = round(set_ratio, 4)
        metrics.DEVICE_BATCH_OCCUPANCY_RATIO.observe(set_ratio, op=op, axis="sets")
        metrics.DEVICE_BATCH_WASTED_LANES.inc(max(0, nb - n_live), op=op, axis="sets")
    if dispatched and mesh and shard_live and nb > 0 and len(shard_live) > 1:
        # Per-shard view: each device's live/padded ratio on this dispatch.
        # Histogram axis "sets_per_shard" keeps the batch-level "sets"
        # signal clean; the flight record carries the exact split.
        rows = nb // len(shard_live)
        ratios = [round(min(1.0, live / rows), 4) if rows else 0.0
                  for live in shard_live]
        entry["shard_live"] = [int(v) for v in shard_live]
        entry["occupancy_per_shard"] = ratios
        for r in ratios:
            metrics.DEVICE_BATCH_OCCUPANCY_RATIO.observe(
                r, op=op, axis="sets_per_shard")
    rows = nb if key_rows is None else int(key_rows)
    if dispatched and live_keys is not None and len(shape) >= 2 and rows * shape[1] > 0:
        lanes = rows * shape[1]
        key_ratio = min(1.0, live_keys / lanes)
        entry["live_keys"] = int(live_keys)
        entry["key_rows"] = rows
        entry["key_slots"] = lanes
        entry["occupancy_keys"] = round(key_ratio, 4)
        metrics.DEVICE_BATCH_OCCUPANCY_RATIO.observe(key_ratio, op=op, axis="keys")
        metrics.DEVICE_BATCH_WASTED_LANES.inc(
            max(0, lanes - live_keys), op=op, axis="keys"
        )
    if host_fallback:
        reason = fallback_reason or "unknown"
        with _FALLBACKS_LOCK:
            # process-boundary: ok(scope seam: per-node views in telemetry_scope)
            _FALLBACKS[reason] = _FALLBACKS.get(reason, 0) + 1
    # Node attribution (fleet runs): a batch dispatched under an active
    # telemetry scope is stamped with its node, mirrored into the scope's
    # flight tail, and cross-referenced as a (node, seq) flight_seq pair —
    # a plain int seq is ambiguous once N nodes share the process ring.
    from . import telemetry_scope

    scope = telemetry_scope.current()
    if scope is not None:
        entry["node"] = scope.node_id
    entry = FLIGHT_RECORDER.record(entry)
    if scope is not None:
        scope.note_flight(entry)
        fseq = (scope.node_id, entry["seq"])
    else:
        fseq = entry["seq"]
    # Every dispatched batch joins the incident journal with its
    # flight_seq, so a postmortem bundle's journal window cross-references
    # the ring (and, via trace_id, the span tree) record-for-record.
    blackbox.emit("device_batch", "dispatch", trace_id=entry["trace_id"],
                  flight_seq=fseq, op=op, shape=entry["shape"],
                  n_live=int(n_live), verdict=verdict,
                  host_fallback=bool(host_fallback) or None,
                  fallback_reason=fallback_reason,
                  breaker_state=breaker_state)
    return entry


def host_fallback_counts() -> Dict[str, int]:
    with _FALLBACKS_LOCK:
        return dict(_FALLBACKS)


# Fused-epoch duty-cache priming tally (per_epoch._prime_duty_caches): how
# often the fused boundary's precomputed shuffling/proposers actually
# seeded the caches vs. were discarded, by reason.  A climbing discard
# count means the device did the O(n) shuffle work and the node threw it
# away — the first triage stop when epoch-boundary latency regresses with
# the fused path on (see OBSERVABILITY.md).
_BOUNDARY_PRIMES: Dict[str, int] = {}
# process-boundary: ok(scope seam: per-node views live in telemetry_scope)
_BOUNDARY_PRIMES_LOCK = threading.Lock()


def note_boundary_prime(seeded: bool, reason: str) -> None:
    key = f"{'seeded' if seeded else 'discarded'}:{reason}"
    with _BOUNDARY_PRIMES_LOCK:
        # process-boundary: ok(scope seam: per-node views in telemetry_scope)
        _BOUNDARY_PRIMES[key] = _BOUNDARY_PRIMES.get(key, 0) + 1


def boundary_prime_counts() -> Dict[str, int]:
    with _BOUNDARY_PRIMES_LOCK:
        return dict(_BOUNDARY_PRIMES)


def recent_inflight_seconds(op: str, min_samples: int = 3,
                            window: int = 32) -> Optional[float]:
    """Median observed in-flight duration (dispatch + wait stages) of the
    last ``window`` dispatched ``op`` batches, or None below
    ``min_samples``.  The adaptive-linger feedback signal: while a batch is
    in flight the pipeline's pending queue fills for free, so the observed
    in-flight duration is exactly how long a linger is throughput-neutral
    (device_pipeline derives its effective linger from this)."""
    durations: List[float] = []
    for r in FLIGHT_RECORDER.recent(limit=window, op=op):
        stages = r.get("stages_s")
        # compiled batches carry jit time in their dispatch stage (minutes
        # on CPU) — poison for a linger signal meant to track steady state
        if not stages or r.get("host_fallback") or r.get("compiled"):
            continue
        d = stages.get("dispatch", 0.0) + stages.get("wait", 0.0)
        if d > 0:
            durations.append(d)
    if len(durations) < min_samples:
        return None
    durations.sort()
    return durations[len(durations) // 2]


# ------------------------------------------------------------ program stages

_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: The attributes that name called computations; operands precede them.
_HLO_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                        r"false_computation|branch_computations)=")
_HLO_REF = re.compile(r"%([\w.\-]+)")


def hlo_stage_map(hlo_text: str, stages) -> Dict[str, str]:
    """HLO instruction name -> stage, for an optimised HLO module's text.

    An instruction's stage is the innermost of ``stages`` in its ``op_name``
    metadata (a ``jax.named_scope`` path).  One without it takes the stage
    of the computations it calls (a fusion, a loop), else of its operands,
    else of the instruction that calls its computation."""
    stages = set(stages)
    comp = None
    instrs: Dict[str, dict] = {}
    members: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            head = line.strip()
            if head.endswith("{") and "(" in head:
                comp = head.split("(", 1)[0].replace("ENTRY", "").strip()
                comp = comp.lstrip("%")
                members.setdefault(comp, [])
            continue
        name, rest = m.groups()
        op_name = _HLO_OP_NAME.search(rest)
        stage = None
        if op_name:
            for part in op_name.group(1).split("/"):
                if part in stages:
                    stage = part
        body = rest.split("metadata=", 1)[0]
        at = _HLO_CALLS.search(body)
        head, tail = (body[:at.start()], body[at.start():]) if at else (body, "")
        instrs[name] = {"stage": stage, "comp": comp,
                        "calls": _HLO_REF.findall(tail),
                        "operands": _HLO_REF.findall(head)}
        members.setdefault(comp, []).append(name)
    caller: Dict[str, str] = {}
    for name, ins in instrs.items():
        for c in ins["calls"]:
            caller.setdefault(c, name)

    def callee_stage(comps, seen):
        for c in comps:
            if c in seen:
                continue
            seen.add(c)
            for member in members.get(c, ()):
                if instrs[member]["stage"]:
                    return instrs[member]["stage"]
            for member in members.get(c, ()):
                found = callee_stage(instrs[member]["calls"], seen)
                if found:
                    return found
        return None

    changed = True
    while changed:
        changed = False
        for name, ins in instrs.items():
            if ins["stage"]:
                continue
            stage = callee_stage(ins["calls"], set())
            if stage is None:
                stage = next((instrs[o]["stage"] for o in ins["operands"]
                              if o in instrs and instrs[o]["stage"]), None)
            if stage is None and ins["comp"] in caller:
                stage = instrs[caller[ins["comp"]]]["stage"]
            if stage:
                ins["stage"] = stage
                changed = True
    return {name: ins["stage"] for name, ins in instrs.items() if ins["stage"]}


#: (op, shape) -> its stage map, filled when a reader first asks.
_STAGE_MAPS: Dict[Tuple[str, Tuple[int, ...]], Dict[str, str]] = {}
# process-boundary: ok(scope seam: compiled programs are per process)
_STAGE_MAPS_LOCK = threading.Lock()


def program_stages(op: str, shape: Tuple[int, ...]) -> Optional[Dict[str, str]]:
    """Map each HLO instruction of the single-device ``op`` program compiled
    at bucket ``shape`` to its stage (the ``STAGES`` scopes of its module),
    from the ``op_name`` metadata of the executable's optimised HLO.  The
    names are those a profiler's ``XLA Ops`` events carry.  None for an op
    without named stages.  Computed when a reader first asks, then cached;
    never called on the request path."""
    if op == "bls_verify":
        from .ops import verify as program
    elif op == "kzg_batch":
        from .ops import kzg_device as program
    else:
        return None
    key = (op, tuple(int(s) for s in shape))
    with _STAGE_MAPS_LOCK:
        cached = _STAGE_MAPS.get(key)
    if cached is None:
        text = program.lower_for_stages(key[1]).compile().as_text()
        cached = hlo_stage_map(text, program.STAGES)
        with _STAGE_MAPS_LOCK:
            # process-boundary: ok(scope seam: compiled programs are per process)
            _STAGE_MAPS[key] = cached
    return cached


# ------------------------------------------------------------- device memory


def device_memory_stats() -> List[dict]:
    """Per-device ``memory_stats()`` snapshot.  CPU devices report nothing
    (None / NotImplementedError); the summary still lists them so "no
    memory telemetry on this platform" is explicit, not absent."""
    try:
        import jax

        devices = jax.devices()
    except Exception:
        return []
    out = []
    for d in devices:
        entry: Dict[str, Any] = {
            "id": int(d.id),
            "platform": d.platform,
            "kind": getattr(d, "device_kind", ""),
        }
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            entry["stats"] = {
                k: int(v) for k, v in stats.items() if isinstance(v, (int, float))
            }
        out.append(entry)
    return out


def _collect_device_memory() -> None:
    """Scrape-time collector: mirror memory_stats onto gauges."""
    for entry in device_memory_stats():
        for stat, value in entry.get("stats", {}).items():
            if "bytes" in stat:
                metrics.DEVICE_MEMORY_BYTES.set(
                    value, device=str(entry["id"]), stat=stat
                )


metrics.register_collector(_collect_device_memory)


# ------------------------------------------------------------------ summary


def _percentiles(values: List[float]) -> Optional[dict]:
    if not values:
        return None
    values = sorted(values)
    n = len(values)

    def pick(q: float) -> float:
        return values[min(n - 1, int(q * n))]

    return {
        "n": n,
        "min": round(values[0], 4),
        "p50": round(pick(0.50), 4),
        "p90": round(pick(0.90), 4),
        "p99": round(pick(0.99), 4),
        "max": round(values[-1], 4),
    }


def summary() -> dict:
    """The ``GET /lighthouse/device`` payload: compiled-program inventory,
    occupancy percentiles over the flight-recorder window, host-fallback
    tallies, device memory."""
    records = FLIGHT_RECORDER.recent(limit=FLIGHT_RECORDER.capacity)
    # Percentiles are grouped per op, matching the labeled histograms: an
    # unpadded op (epoch_deltas always runs at occupancy 1.0) must not
    # dilute the padding-waste signal of the bucketed ones.
    occ: Dict[str, dict] = {}
    for r in records:
        if "occupancy_sets" not in r and "occupancy_keys" not in r:
            continue
        per_op = occ.setdefault(r["op"], {"sets": [], "keys": []})
        if "occupancy_sets" in r:
            per_op["sets"].append(r["occupancy_sets"])
        if "occupancy_keys" in r:
            per_op["keys"].append(r["occupancy_keys"])
    occ = {
        op: {axis: _percentiles(vals) for axis, vals in axes.items() if vals}
        for op, axes in occ.items()
    }
    from . import autotune, device_mesh, device_pipeline, device_supervisor

    return {
        "programs": COMPILE_CACHE.inventory(),
        # Self-tuning control plane (autotune.py): mode + live vocabulary
        # overlay — the flight recorder below is its evidence stream, and
        # GET /lighthouse/autotune is the full decision log.
        "autotune": {
            "mode": autotune.mode(),
            "overlay": {k: list(v) for k, v in autotune.overlay().items()},
        },
        # Mesh-sharding subsystem (device_mesh.py): topology, per-device
        # breakers, reshard count — the first stop when one chip is sick.
        "mesh": device_mesh.summary(),
        "occupancy": occ,
        "host_fallbacks": host_fallback_counts(),
        # Fused epoch boundary: duty-cache priming outcomes (seeded vs
        # discarded, by reason) — empty until the fused path has run.
        "boundary_primes": boundary_prime_counts(),
        # Async device pipeline (device_pipeline.py): pending depth, fill
        # and linger of the coalescing layer feeding the batches above
        # (None until a pipeline has started in this process).
        "pipeline": device_pipeline.summary(),
        "flight_recorder": {
            "capacity": FLIGHT_RECORDER.capacity,
            "stored": len(FLIGHT_RECORDER),
            "recorded_total": FLIGHT_RECORDER.recorded_total,
        },
        "memory": device_memory_stats(),
        # Supervisor surface (device_supervisor.py): per-op breaker state,
        # trip/probe counters, and the watchdog deadlines in force — the
        # first thing to check when host_fallbacks is climbing.
        "supervisor": device_supervisor.summary(),
    }


def reset_for_tests() -> None:
    """Clear all module state (compile mirror, ring, fallback tallies)."""
    # process-boundary: ok(scope seam: test-only reset of per-process state)
    COMPILE_CACHE.clear()
    # process-boundary: ok(scope seam: test-only reset of per-process state)
    FLIGHT_RECORDER.clear()
    with _FALLBACKS_LOCK:
        # process-boundary: ok(scope seam: test-only reset of per-process state)
        _FALLBACKS.clear()
    with _BOUNDARY_PRIMES_LOCK:
        # process-boundary: ok(scope seam: test-only reset of per-process state)
        _BOUNDARY_PRIMES.clear()


# ----------------------------------------------------------------- profiler


class ProfilerUnavailable(RuntimeError):
    """The device tracer cannot produce anything useful here (CPU)."""


class ProfilerBusy(RuntimeError):
    """A capture is already in flight — one at a time."""


# process-boundary: ok(scope seam: profiler capture is per process by design)
_PROFILE_LOCK = threading.Lock()

#: Dump directories retained under the profile root — older captures are
#: pruned before each new one, so repeated POSTs can't fill /tmp.
PROFILE_RETAIN = int(os.environ.get("LIGHTHOUSE_TPU_PROFILE_RETAIN", "8"))


def _prune_profiles(root: str) -> None:
    import shutil

    try:
        dumps = sorted(
            e for e in os.listdir(root) if e.startswith("profile_")
        )
    except OSError:
        return
    for stale in dumps[: max(0, len(dumps) - (PROFILE_RETAIN - 1))]:
        shutil.rmtree(os.path.join(root, stale), ignore_errors=True)


def capture_profile(seconds: float, out_root: Optional[str] = None) -> dict:
    """Capture ``seconds`` of ``jax.profiler.trace`` into a fresh directory
    and return its path (loadable in Perfetto / TensorBoard).

    Raises :class:`ProfilerUnavailable` on CPU — the device tracer has no
    device activity to record there, and libtpu/plugin tracing is absent —
    unless ``LIGHTHOUSE_TPU_FORCE_PROFILER=1`` (CI exercising the path).
    Raises :class:`ProfilerBusy` when a capture is already running.
    """
    seconds = max(0.05, min(float(seconds), MAX_PROFILE_SECONDS))
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu" and not os.environ.get("LIGHTHOUSE_TPU_FORCE_PROFILER"):
        raise ProfilerUnavailable(
            "device profiling is unavailable on the cpu backend "
            "(no device tracer; set LIGHTHOUSE_TPU_FORCE_PROFILER=1 to force)"
        )
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture is already in progress")
    try:
        root = out_root or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "lighthouse_tpu_profiles"
        )
        _prune_profiles(root)
        path = os.path.join(root, f"profile_{int(time.time() * 1000)}")
        os.makedirs(path, exist_ok=True)
        t0 = time.perf_counter()
        try:
            with jax.profiler.trace(path):
                time.sleep(seconds)
        except Exception as e:
            raise ProfilerUnavailable(f"jax.profiler.trace failed: {e}")
        return {
            "path": path,
            "seconds": round(time.perf_counter() - t0, 3),
            "platform": platform,
            "hint": "load the trace in Perfetto (ui.perfetto.dev) or "
                    "`tensorboard --logdir` on the returned path",
        }
    finally:
        _PROFILE_LOCK.release()
