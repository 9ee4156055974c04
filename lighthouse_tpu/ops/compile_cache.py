"""Persistent XLA compilation cache + ahead-of-time bucket warmup.

The fused BLS verifier pays 20-165 s of trace+compile per (n_bucket,
k_bucket) shape (PERF.md round 5, device_telemetry measures it per shape)
— and before this module every PROCESS paid it again: bench.py, the
scripts and the test conftest each carried their own copy of the
``jax_compilation_cache_dir`` config block, while the actual node startup
path (``client.ClientBuilder.build`` / the CLI) had none, so a restarted
node recompiled everything.  This module is the one shared implementation:

- :func:`configure_persistent_cache` points jax's persistent compile cache
  at one stable on-disk directory: ``JAX_COMPILATION_CACHE_DIR`` when the
  environment sets it (then it is the ONLY cache directory), else
  ``<repo>/.jax_cache`` — so cold compiles are paid once per *binary*, not
  once per process restart.
- :func:`warmup_standard_buckets` ahead-of-time compiles the standard
  dispatch buckets (``jit(...).lower(...).compile()`` on abstract shapes —
  no example batch needed) before traffic arrives, classifying each bucket
  as a persistent-cache ``hit`` (fast deserialize) or ``miss`` (real
  compile) and feeding the existing compile-cache telemetry
  (``device_program_compiles_total`` / ``device_aot_warmup_total``; the
  mirror is pre-seeded so the bucket's first production dispatch is not
  misattributed as a compile).
- :func:`maybe_warmup_from_env` is the startup hook: opt-in via
  ``LIGHTHOUSE_TPU_AOT_WARMUP=1`` (bucket list override
  ``LIGHTHOUSE_TPU_AOT_BUCKETS="128x32,4096x32"``), run on a daemon thread
  so node startup never blocks on the compiler.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence, Tuple

AOT_WARMUP_ENV = "LIGHTHOUSE_TPU_AOT_WARMUP"
AOT_BUCKETS_ENV = "LIGHTHOUSE_TPU_AOT_BUCKETS"

#: Production standard buckets warmed by default: the headline config and
#: the 4096-set top bucket (ops/verify.py N_BUCKETS[-1]).
DEFAULT_WARMUP_BUCKETS: Tuple[Tuple[int, int], ...] = ((128, 32), (4096, 32))

#: A warmup faster than this is a persistent-cache deserialize, not a
#: compile — the real compiles of these programs take tens of seconds on
#: every platform measured (PERF.md).
WARMUP_HIT_THRESHOLD_S = 5.0

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def configure_persistent_cache() -> str:
    """Point jax's persistent compile cache at :func:`default_cache_dir`
    and return it.  Raises if jax rejects the config: a node that cannot
    cache recompiles everything on restart, and must say so."""
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path


def _env_buckets() -> Optional[List[Tuple[int, ...]]]:
    """Parse ``LIGHTHOUSE_TPU_AOT_BUCKETS`` ("128x32,4096x32"; a bucket whose
    sets span key rows has four dims, "16x2048x64x16"; case-insensitive
    separator, empty parts skipped).  Raises ValueError naming the variable on
    garbage — callers decide whether to fall back."""
    raw = os.environ.get(AOT_BUCKETS_ENV, "").strip()
    if not raw:
        return None
    buckets = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            bucket = tuple(int(d) for d in part.lower().split("x"))
            if len(bucket) not in (2, 4):
                raise ValueError(part)
        except ValueError:
            raise ValueError(
                f"{AOT_BUCKETS_ENV}={raw!r}: expected e.g. \"128x32,4096x32\""
            ) from None
        buckets.append(bucket)
    return buckets or None


def _cache_file_count() -> Optional[int]:
    """Number of entries in the live persistent-cache dir, or None when the
    cache is unset/unreadable (then hit/miss falls back to wall clock)."""
    import jax

    try:
        path = jax.config.jax_compilation_cache_dir
        if not path:
            return None
        return len(os.listdir(path))
    except Exception:
        return None


def _bucket_shape_structs(nb: int, kb: int, nr: Optional[int] = None,
                          rb: Optional[int] = None):
    """Abstract argument shapes of ``_device_verify`` for one bucket — the
    exact dtypes/shapes ``build_batch`` marshals, with no host crypto.
    ``(nb, kb)``: one key row a set; ``(nb, kb, nr, rb)``: ``nr`` key rows,
    up to ``rb`` of them a set (``verify.bucket_shape``)."""
    import jax
    import numpy as np

    i32 = np.int32
    pk = tuple(jax.ShapeDtypeStruct((nb if nr is None else nr, kb, 25), i32)
               for _ in range(3))
    sig = tuple(jax.ShapeDtypeStruct((nb, 2, 25), i32) for _ in range(3))
    msg = tuple(jax.ShapeDtypeStruct((nb, 2, 25), i32) for _ in range(2))
    wbits = jax.ShapeDtypeStruct((nb, 64), i32)
    live = jax.ShapeDtypeStruct((nb,), np.bool_)
    rows = None if nr is None else jax.ShapeDtypeStruct((nb, rb), i32)
    return pk, sig, msg, wbits, live, rows


def kzg_shape_structs(nb: int):
    """Abstract argument shapes of ``_device_kzg_batch`` for bucket ``nb``
    (``kzg_device._build_kzg_batch`` marshals exactly these)."""
    import jax
    import numpy as np

    i32 = np.int32
    g1 = tuple(jax.ShapeDtypeStruct((nb, 25), i32) for _ in range(3))
    bits = jax.ShapeDtypeStruct((nb, 256), i32)
    g2 = tuple(jax.ShapeDtypeStruct((2, 25), i32) for _ in range(2))
    return g1, g1, bits, bits, jax.ShapeDtypeStruct((256,), i32), g2, g2


def boundary_shape_structs(nb: int):
    """Abstract argument shapes of ``_boundary_kernel`` for registry bucket
    ``nb`` at mainnet slots-per-epoch / shuffle rounds.  Build them inside
    ``jax.enable_x64(True)``, as the dispatch does."""
    import jax
    import numpy as np

    from .shuffle_device import PROPOSER_CANDIDATES

    s, r = 32, 90
    chunks = max(1, (nb + 255) // 256)
    i64 = jax.ShapeDtypeStruct((nb,), np.int64)
    return (
        [i64] * 4
        + [jax.ShapeDtypeStruct((nb,), np.bool_)]
        + [i64] * 5
        + [jax.ShapeDtypeStruct((nb,), np.int32)]
        + [jax.ShapeDtypeStruct((r,), np.int32),
           jax.ShapeDtypeStruct((r, chunks * 32), np.uint8),
           jax.ShapeDtypeStruct((s, 8), np.uint32),
           jax.ShapeDtypeStruct((s, r), np.int32),
           jax.ShapeDtypeStruct((s, PROPOSER_CANDIDATES), np.int32)]
        + [jax.ShapeDtypeStruct((), np.int64)] * 16
        + [jax.ShapeDtypeStruct((), np.int32)]
    )


def _aot_compile(op: str, shape: Tuple[int, ...], lower_thunk,
                 hit_threshold_s: float = WARMUP_HIT_THRESHOLD_S) -> dict:
    """One ahead-of-time compile: run ``lower_thunk`` (an abstract
    ``.lower(...).compile()`` call), classify hit (persistent-cache
    deserialize) vs miss (real XLA compile) by watching the cache dir, and
    feed the compile-mirror telemetry (``device_telemetry.note_warmup``).
    Returns the per-shape record warmup callers aggregate."""
    import time as _time

    from .. import device_telemetry
    from ..logs import get_logger

    log = get_logger("compile_cache")
    label = "x".join(str(int(s)) for s in shape)
    record = {"op": op, "shape": label}
    t0 = _time.perf_counter()
    cache_files_before = _cache_file_count()
    try:
        lower_thunk()
    except Exception as e:  # noqa: BLE001 — warmup must never kill startup
        record["seconds"] = round(_time.perf_counter() - t0, 3)
        record["outcome"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        log.warning("AOT warmup failed", **record)
        return record
    dt = _time.perf_counter() - t0
    # A real compile writes new entries into the persistent cache dir
    # (min_compile_time 1.0s); a deserialize does not.  The wall-clock
    # threshold is the fallback when the dir is not observable.
    cache_files_after = _cache_file_count()
    if cache_files_before is not None and cache_files_after is not None:
        hit = cache_files_after == cache_files_before
    else:
        hit = dt < hit_threshold_s
    record["seconds"] = round(dt, 3)
    record["outcome"] = "hit" if hit else "miss"
    device_telemetry.note_warmup(op, shape, dt, hit)
    log.info("AOT warmup", **record)
    return record


def aot_warmup_op(op: str, nb: int) -> List[dict]:
    """AOT-compile one op's bucket ``nb`` off the production path — the
    autotune controller's adoption prerequisite (a live-mode bucket is
    only adopted after its compile cost is paid here, never inside a
    caller's dispatch).  Covers the three tunable vocabularies; the epoch
    op warms BOTH leak modes (``in_leak`` forks the compiled program)."""
    import jax
    import numpy as np

    nb = int(nb)
    if op == "bls_verify":
        from .verify import _device_verify

        return [_aot_compile(
            "bls_verify", (nb, 32),
            lambda: _device_verify.lower(
                *_bucket_shape_structs(nb, 32)).compile())]
    if op == "sha256_pairs":
        from .sha256_device import _sha256_64byte_batch

        words = jax.ShapeDtypeStruct((nb, 16), np.uint32)
        return [_aot_compile(
            "sha256_pairs", (nb,),
            lambda: _sha256_64byte_batch.lower(words).compile())]
    if op in ("epoch_deltas", "epoch_deltas_leak"):
        from .epoch_device import _deltas_kernel

        def epoch_thunk(in_leak: bool):
            def thunk():
                with jax.enable_x64(True):
                    i64 = jax.ShapeDtypeStruct((nb,), np.int64)
                    s64 = jax.ShapeDtypeStruct((), np.int64)
                    args = ([i64] * 4
                            + [jax.ShapeDtypeStruct((nb,), np.bool_)]
                            + [i64] * 2 + [s64] * 7)
                    _deltas_kernel.lower(*args, in_leak=in_leak).compile()
            return thunk

        return [
            _aot_compile("epoch_deltas", (nb,), epoch_thunk(False)),
            _aot_compile("epoch_deltas_leak", (nb,), epoch_thunk(True)),
        ]
    if op == "shuffle":
        from .shuffle_device import _shuffle_kernel

        def shuffle_thunk():
            r = 90  # mainnet shuffle_round_count — the production shape
            chunks = max(1, (nb + 255) // 256)
            values = jax.ShapeDtypeStruct((nb,), np.int32)
            pivots = jax.ShapeDtypeStruct((r,), np.int32)
            digests = jax.ShapeDtypeStruct((r, chunks * 32), np.uint8)
            n_live = jax.ShapeDtypeStruct((), np.int32)
            _shuffle_kernel.lower(values, pivots, digests, n_live).compile()

        return [_aot_compile("shuffle", (nb,), shuffle_thunk)]
    if op == "proposer_select":
        from .shuffle_device import PROPOSER_CANDIDATES, _proposer_kernel

        def proposer_thunk():
            with jax.enable_x64(True):
                s, r = 32, 90  # mainnet slots-per-epoch / rounds
                seed_words = jax.ShapeDtypeStruct((s, 8), np.uint32)
                pivots = jax.ShapeDtypeStruct((s, r), np.int32)
                rbytes = jax.ShapeDtypeStruct(
                    (s, PROPOSER_CANDIDATES), np.int32)
                eff = jax.ShapeDtypeStruct((nb,), np.int64)
                i32 = jax.ShapeDtypeStruct((), np.int32)
                i64 = jax.ShapeDtypeStruct((), np.int64)
                _proposer_kernel.lower(
                    seed_words, pivots, rbytes, eff, i32, i64).compile()

        return [_aot_compile("proposer_select", (nb,), proposer_thunk)]
    if op in ("epoch_boundary", "epoch_boundary_leak"):
        from .shuffle_device import _boundary_kernel

        def boundary_thunk(in_leak: bool):
            def thunk():
                with jax.enable_x64(True):
                    _boundary_kernel.lower(
                        *boundary_shape_structs(nb), in_leak=in_leak).compile()
            return thunk

        return [
            _aot_compile("epoch_boundary", (nb,), boundary_thunk(False)),
            _aot_compile("epoch_boundary_leak", (nb,), boundary_thunk(True)),
        ]
    raise ValueError(f"no AOT warmup recipe for op {op!r}")


def warmup_standard_buckets(
    buckets: Optional[Sequence[Tuple[int, ...]]] = None,
    *,
    hit_threshold_s: float = WARMUP_HIT_THRESHOLD_S,
) -> List[dict]:
    """AOT-compile the standard verifier buckets; returns per-bucket records
    ``{"op", "shape", "seconds", "outcome"}`` (outcome hit|miss|error).

    Telemetry rides the existing compile-cache machinery
    (:func:`device_telemetry.note_warmup`), so ``GET /lighthouse/device``
    shows warmed buckets before the first batch arrives.
    """
    from ..logs import get_logger
    from .verify import _device_verify

    log = get_logger("compile_cache")
    if buckets is None:
        try:
            buckets = _env_buckets()
        except ValueError as e:
            # A bad env list must not kill the daemon thread OR silently
            # disable the warmup the operator explicitly enabled: log loud,
            # warm the defaults.
            log.warning("AOT bucket list invalid, warming defaults", error=str(e))
            buckets = None
        buckets = buckets or list(DEFAULT_WARMUP_BUCKETS)
    results: List[dict] = []
    for shape in buckets:
        shape = tuple(int(d) for d in shape)
        results.append(_aot_compile(
            "bls_verify", shape,
            lambda shape=shape: _device_verify.lower(
                *_bucket_shape_structs(*shape)).compile(),
            hit_threshold_s=hit_threshold_s,
        ))
    return results


def maybe_warmup_from_env(*, background: bool = True) -> Optional[threading.Thread]:
    """Startup hook: run the AOT warmup iff ``LIGHTHOUSE_TPU_AOT_WARMUP`` is
    truthy.  Background by default so node startup never blocks on XLA;
    returns the thread (or None when disabled / when run inline)."""
    if os.environ.get(AOT_WARMUP_ENV, "").strip().lower() not in ("1", "true", "yes"):
        return None
    if not background:
        warmup_standard_buckets()
        return None
    thread = threading.Thread(
        target=warmup_standard_buckets, name="aot-warmup", daemon=True
    )
    thread.start()
    return thread
