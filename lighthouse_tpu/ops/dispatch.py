"""The one dispatch seam of the bucketed device programs.

Each op module hands its jitted program to a :class:`Program` and its
marshalled host arguments to :class:`Placed`.  What lies between the host
arrays and the decoded result is here, once:

- the mesh: the batch axis padded to a multiple of the mesh size with the
  program's pad fills, the ``device_mesh.ShardedEntry`` derived from the
  program's ``ops/batch_axes.py`` key, the upload, and a second upload
  after a reshard (the mesh generation);
- the fault points, in order: ``device.compile`` (a shape not seen
  before), ``device.dispatch``, then ``device.result`` on a verdict;
- the timed dispatch and the compile-cache mirror (``note_dispatch``), the
  wait and the verdict, as spans ``<spans>_dispatch/_wait/_verdict`` where
  the program names them and as timers where it does not, into a stage
  dict the worker owns;
- the device supervisor (:meth:`Program.run`: watchdog, one split-batch
  retry whose halves take the same path without a flight record, the
  breaker, the host route) and the flight record (``record_batch``);
- the ``jax.enable_x64`` scope of the int64 programs.

Each op keeps what is its own: its bucket choice and host packing, its
jitted programs, its host golden model and how its outputs decode.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import device_mesh, device_telemetry, fault_injection, metrics, tracing

#: The histograms behind the verifier's stage spans.
_HISTS = {
    "device_batch_dispatch": metrics.DEVICE_DISPATCH_SECONDS,
    "device_batch_wait": metrics.DEVICE_BLOCK_UNTIL_READY_SECONDS,
    "device_batch_verdict": metrics.DEVICE_VERDICT_SECONDS,
}


def _rows(args) -> Tuple[int]:
    """The bucket of an argument tuple: the rows of its first argument."""
    return (int(jax.tree_util.tree_leaves(args[0])[0].shape[0]),)


def _bucket_fields(shape) -> dict:
    """A bucket's span fields: its rows, and where it has a key axis, the
    key width and the key rows."""
    if len(shape) == 1:
        return {"n_bucket": shape[0]}
    return {"n_bucket": shape[0], "k_bucket": shape[1],
            "r_bucket": shape[2] if len(shape) > 2 else shape[0]}


class _Timer:
    """A stage timer for a program without spans: a span's ``fields`` and
    ``duration``, without the trace."""

    __slots__ = ("fields", "start", "duration")

    def __enter__(self):
        self.fields = {}
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.duration = time.perf_counter() - self.start


class Program:
    """A jitted bucketed device program and how the seam dispatches it.

    ``fills``: the pad value of each leading batched argument, a tuple of
    them for a tuple of arrays; a program without fills never shards.
    ``shape``: the bucket of an argument tuple.  ``spans``: the prefix of
    the stage spans, where the program has them.  ``x64``: the program
    takes int64 arrays.  The op and the ``ops/batch_axes.py`` entry come
    from the program itself; its keyword-only parameters are static."""

    def __init__(self, fn, *, fills=None, shape=_rows,
                 spans: Optional[str] = None, x64: bool = False):
        wrapped = fn.__wrapped__
        static = tuple(p.name for p in inspect.signature(wrapped).parameters.values()
                       if p.kind is p.KEYWORD_ONLY)
        self.entry = device_mesh.ShardedEntry(
            f"{fn.__module__.replace('.', '/')}.py:{fn.__name__}", wrapped,
            static_argnames=static)
        self.op = self.entry.op
        self.fn = fn
        self.fills = fills
        self.shape = shape
        self.spans = spans
        self.x64 = x64

    def rows(self, n: int) -> int:
        """The rows a batch of ``n`` dispatches at: ``n`` padded to the mesh
        where this program shards over one."""
        if self.fills is None or not device_mesh.enabled():
            return n
        return device_mesh.pad_rows(n)

    def pad(self, host: tuple, rows: int = 0) -> tuple:
        """``host`` with the rows of each batched argument grown, at least to
        ``rows``, to a multiple of the mesh size, with the program's fills."""
        def grow(a, fill):
            target = device_mesh.pad_rows(max(rows, a.shape[0]))
            return device_mesh.grow_rows(a, target, fill)

        out = list(host)
        for i, fill in enumerate(self.fills):
            a = host[i]
            if isinstance(a, tuple):
                out[i] = tuple(grow(x, f) for x, f in zip(a, fill))
            elif a is not None:
                out[i] = grow(a, fill)
        return tuple(out)

    # ------------------------------------------------------------ the leg

    @contextlib.contextmanager
    def _stage(self, name: str, stages: dict, **fields):
        """Time one stage into ``stages[name]``: the span ``<spans>_<name>``
        (with its histogram), or a timer where the program has no spans."""
        span = self.spans and f"{self.spans}_{name}"
        timer = (tracing.span(span, hist=_HISTS.get(span), **fields)
                 if span else _Timer())
        try:
            with timer as sp:
                yield sp
        finally:
            stages[name] = sp.duration

    def execute(self, placed: "Placed", *, n_live: int, stages: dict,
                state: dict, verdict: Optional[Callable] = None,
                finish: Optional[Callable] = None, op: Optional[str] = None,
                static: Optional[dict] = None):
        """The device leg of one batch: the fault points, the timed dispatch,
        the wait, then ``verdict(outputs)`` in a stage of its own, or
        ``finish(host outputs, n_live)``.  Stage times go to ``stages``;
        ``state["compiled"]`` marks the first dispatch of a shape."""
        op = op or self.op
        placed.current()
        shape, mesh = placed.shape, placed.mesh
        if fault_injection.ACTIVE:
            if not device_telemetry.COMPILE_CACHE.seen(op, shape, mesh=mesh):
                fault_injection.check("device.compile", op=op)
            fault_injection.check("device.dispatch", op=op)
        fields = _bucket_fields(shape)
        fn = self.entry if mesh else self.fn
        with self._stage("dispatch", stages, mesh=mesh, **fields) as sp:
            out = fn(*placed.args, **(static or {}))
        # A shape's first dispatch traces and compiles inside the call: its
        # duration is the compile-time observation for that shape.
        if device_telemetry.note_dispatch(op, shape, sp.duration, mesh=mesh):
            sp.fields["compiled"] = state["compiled"] = True
        if verdict is None:
            with self._stage("wait", stages):
                out = jax.device_get(out)
            return out if finish is None else finish(out, n_live)
        with self._stage("wait", stages, **fields):
            jax.block_until_ready(out)
        with self._stage("verdict", stages):
            ok = verdict(out)
            if (fault_injection.ACTIVE
                    and fault_injection.fire("device.result", op=op) == "corrupt"):
                tracing.annotate(corrupted_verdict=True)
                ok = False
        return ok

    def _half(self, half: Callable, lo: int, hi: int, verdict, finish):
        """One half of a split retry, at its own bucket: live rows
        ``[lo, hi)`` marshalled by ``half`` (None: rejected on the host)."""
        host = half(lo, hi)
        if host is None:
            return False
        return self.execute(Placed(self, host), n_live=hi - lo, stages={},
                            state={}, verdict=verdict, finish=finish)

    # ------------------------------------------------------- the batches

    def run(self, batch, *, n_live: int, host_fn: Optional[Callable] = None,
            verdict: Optional[Callable] = None,
            finish: Optional[Callable] = None,
            half: Optional[Callable] = None,
            combine: Optional[Callable] = None, bucket=None, **record):
        """One supervised, flight-recorded batch.

        ``batch`` is a :class:`Placed`, or a callable that marshals and
        places it on the supervisor's worker, so an open breaker never
        touches the device and a reshard retry places it anew.  ``host_fn``
        is the host golden model; ``half(lo, hi)`` marshals live rows
        ``[lo, hi)`` for the split retry and ``combine`` joins the halves'
        results.  ``bucket`` is the record's shape where the batch never
        reached placement; ``record`` adds fields to the record."""
        from .. import device_supervisor

        lazy = callable(batch)
        stages = {} if lazy else {"setup": batch.setup_s}
        # The worker writes its stage times into dicts it owns and hands them
        # over when the leg ends.  They are merged only when it finished:
        # after a dispatch timeout the abandoned worker may still be writing.
        holder: dict = {}

        def device_fn():
            stages_w: dict = {}
            state_w: dict = {}
            try:
                placed = holder["placed"] = batch() if lazy else batch
                if lazy and placed.setup_s is not None:
                    stages_w["setup"] = placed.setup_s
                out = self.execute(placed, n_live=n_live, stages=stages_w,
                                   state=state_w, verdict=verdict,
                                   finish=finish)
                holder["done"] = True  # the device has read every input
                return out
            finally:
                holder["stages"], holder["state"] = stages_w, state_w

        def split_fn():
            mid = n_live // 2
            if mid == 0:
                raise ValueError(f"{self.op}: a batch of one cannot split")
            return [functools.partial(self._half, half, lo, hi, verdict, finish)
                    for lo, hi in ((0, mid), (mid, n_live))]

        info: dict = {}
        result = device_supervisor.run(
            self.op, device_fn, host_fn=host_fn,
            split_fn=None if half is None else split_fn,
            combine_fn=combine, info=info)
        reason = info.get("fallback_reason")
        state: dict = {}
        if reason != "dispatch_timeout":
            stages.update(holder.get("stages") or {})
            state = holder.get("state") or {}
        if lazy:
            batch = None if reason == "dispatch_timeout" else holder.get("placed")
        elif holder.get("done"):
            batch.release()
        self._record(self.op, batch, n_live, stages, state, info, bucket,
                     verdict=None if verdict is None else bool(result),
                     **record)
        return result

    def call(self, host: tuple, *, n_live: int, rows: int = 0,
             op: Optional[str] = None, static: Optional[dict] = None):
        """One flight-recorded batch dispatched and waited for on the calling
        thread, for the epoch programs, whose callers supervise them.  The
        batched arguments grow to ``rows`` at least; returns the host
        copies of the outputs."""
        stages: dict = {}
        state: dict = {}
        with jax.enable_x64(True) if self.x64 else contextlib.nullcontext():
            placed = Placed(self, host, rows)
            out = self.execute(placed, n_live=n_live, stages=stages,
                               state=state, op=op, static=static)
        self._record(op or self.op, placed, n_live, stages, state, {})
        return out

    def _record(self, op: str, placed: Optional["Placed"], n_live: int,
                stages: dict, state: dict, info: dict, bucket=None,
                **fields) -> None:
        shape = placed.shape if placed is not None else bucket
        mesh = placed.mesh if placed is not None else 0
        reason = info.get("fallback_reason")
        rec = device_telemetry.record_batch(
            op=op,
            shape=shape,
            n_live=n_live,
            key_rows=shape[2] if len(shape) > 2 else None,
            stages=stages or None,
            host_fallback=info.get("route") == "host",
            fallback_reason=reason,
            trace_id=device_telemetry.active_trace_id(),
            compiled=state.get("compiled", False),
            breaker_state=info.get("breaker_state"),
            # breaker-OPEN batches never reached the device: keep them out
            # of the occupancy and wasted-lane tuning data.
            dispatched=reason != "breaker_open",
            mesh=mesh,
            shard_live=(self.entry.shard_live_counts(n_live, shape[0])
                        if mesh else None),
            **fields,
        )
        # Reverse link: the enclosing span carries the batch's flight seq.
        tracing.annotate(flight_seq=rec["seq"])
        if rec["host_fallback"]:
            tracing.annotate(host_fallback=True)


class Placed:
    """A batch's host arguments and their upload: single-device arrays, or
    the batch axis padded to the mesh and sharded over it.  The host copy is
    kept so that a reshard since the upload pads and uploads again."""

    __slots__ = ("program", "host", "rows", "args", "mesh", "generation",
                 "shape", "setup_s")

    def __init__(self, program: Program, host: tuple, rows: int = 0):
        self.program, self.host, self.rows = program, host, rows
        self.setup_s: Optional[float] = None
        self.place()

    def place(self) -> "Placed":
        p = self.program
        meshed = p.fills is not None and device_mesh.enabled()
        # The generation is read before the pad and the upload: a reshard
        # landing mid-place leaves the batch tagged with the old topology,
        # so current() places it again instead of dispatching arrays
        # sharded for devices that are gone.
        self.generation = device_mesh.generation()
        host = self.host if p.fills is None else p.pad(self.host, self.rows)
        if meshed:
            self.args, self.mesh = p.entry.place(*host), device_mesh.size()
        else:
            self.args = jax.tree_util.tree_map(jnp.asarray, host)
            self.mesh = 0
        self.shape = p.shape(self.args)
        return self

    def current(self) -> None:
        """Place again after any topology change since the last placement
        (the mesh enabled, disabled or resharded: each bumps the
        generation)."""
        if device_mesh.generation() != self.generation:
            self.place()

    def release(self) -> None:
        """Drop the arrays once the device is done with them."""
        self.host = self.args = None
