"""Batched ``verify_signature_sets`` as a single fused TPU program.

The device program implements the batch-verification equation of the
reference's hot loop (``crypto/bls/src/impls/blst.rs:35-117``):

    e(-g1, sum_i [r_i] sig_i) * prod_i e([r_i] aggpk_i, H(m_i)) == 1

entirely on device: per-set pubkey aggregation (complete-formula tree sum over a
padded key axis), 64-bit random-weight scalar multiplications on G1 and G2, the
cofactor clearing of each message's hash, the batched Miller loop, one shared
final exponentiation.  The host side keeps the responsibilities the reference
keeps on the "trusted" side: CSPRNG weights (blst.rs:52-57 — randomness must
not come from the device), signature subgroup/infinity checks, hash-to-curve
up to the point of E2 before its cofactor is cleared, shape bucketing.

Shape discipline: programs are compiled per (n_sets_bucket, max_keys_bucket);
batches are padded with identity points + zero weights, which flow through the
complete formulas and masked Miller loop as exact neutral elements.  A set
wider than the top key bucket (an Electra on-chain aggregate spans up to 64
committees) is laid over several key rows of the top bucket's width, and the
program sums each set's row sums; the bucket is then (n_sets_bucket,
row_width, key_rows_bucket, rows_per_set_bucket).

Edge cases (parity with the host backend, tests/test_backend_jax.py):
 - empty batch, missing/out-of-subgroup signature, empty pubkey list -> False on host
 - aggregate pubkey at infinity -> its pair contributes only F_{p^6} factors,
   which the final exponentiation kills (no special-casing needed)
 - weighted-signature-sum at infinity (adversarially unreachable): detected via
   the returned W_z limbs and re-verified on the host golden model
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import autotune, device_supervisor, metrics, tracing
from ..crypto.bls import curve
from ..crypto.bls.backends.host import _rand_scalars
from ..crypto.bls.fields import Fq2
from ..crypto.bls.hash_to_curve import hash_to_g2_uncleared_many
from ..crypto.bls.params import DST, G1_X, G1_Y, P
from . import dispatch, ec, fq, pairing, tower

_NEG_G1 = ec.g1_to_limbs((curve.G1[0], -curve.G1[1]))
_G2_GEN_AFF = (
    tower.fq2_to_limbs(curve.G2[0]),
    tower.fq2_to_limbs(curve.G2[1]),
)


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds max bucket {buckets[-1]}")


#: The 4096-set top bucket is the PRODUCTION standard bucket (PERF round 5:
#: the chip executes 1x1 and 128x32 in nearly the same wall time, so while
#: latency-dominated the x32 batch is near-free; 4096x32 is compile-safe,
#: .perf/big_buckets.json).  Batches larger than the top bucket chunk
#: through :data:`MAX_SETS_PER_DISPATCH`-set dispatches instead of raising.
N_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
K_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048)
MAX_SETS_PER_DISPATCH = N_BUCKETS[-1]

def _aot_warmup(nb: int) -> None:
    from .compile_cache import aot_warmup_op

    aot_warmup_op("bls_verify", nb)


# Enroll the set-axis vocabulary in the self-tuning control plane
# (autotune.py): live mode may overlay midpoint buckets below the static
# top (N_BUCKETS stays the floor, MAX_SETS_PER_DISPATCH the ceiling) —
# though this ratio-2 vocabulary has no real gaps, so in practice the
# controller's densify heuristic never fires here and the registration
# exists so a FUTURE vocabulary edit is tunable without re-wiring.  The
# budget key and the AOT warmup cover the STANDARD 32-key tier only: an
# editor introducing a real gap here must extend both to every K tier
# the new bucket serves, or off-tier dispatches pay an on-path compile
# through an unaudited lowering (today any adoption is refused — no
# committed budget key exists for a midpoint).
autotune.register_vocabulary(
    "bls_verify", N_BUCKETS,
    telemetry_ops=("bls_verify",),
    budget_key=lambda nb: f"bls_verify|{fq.active_fq_backend()}|{nb}x32|-",
    warmup=_aot_warmup,
)


def _set_sums(row_sums, rows):
    """Each set's aggregate key: the sum of its rows' sums.  ``rows`` is
    (N, RB) int32, the key rows of each set, -1 where a set has no more;
    those slots read the identity, and a complete-formula tree sums each
    set's RB slots."""
    taken = tuple(c[jnp.maximum(rows, 0)] for c in row_sums)  # (N, RB, 25)
    pts = ec.point_select(rows >= 0, taken, ec.identity(ec.G1_OPS))
    return ec.tree_sum(ec.G1_OPS, pts, axis=1)


def _h2c_clear(msg):
    """H(m_i) = h_eff·Q_i for the affine points Q_i of ``msg``, affine again
    (one batched inversion): the host hashes each message to E2 and stops
    there.  Pad lanes (the generator) are cleared too; ``live`` masks them
    out of the pairing."""
    one = jnp.broadcast_to(tower.FQ2_ONE, msg[0].shape)
    h = ec.g2_clear_cofactor((msg[0], msg[1], one))
    zi = tower.fq2_inv(h[2])
    return tuple(tower.fq2_mul_many([(h[0], zi), (h[1], zi)]))


@jax.jit
def _device_verify(pk, sig, msg, wbits, live, rows=None):
    """The fused device program.

    pk:   G1 projective, coords (R, K, 25) — key rows, identity-padded; row i
          is set i's keys (R == N) unless ``rows`` is given
    sig:  G2 projective, coords (N, 2, 25)
    msg:  G2 affine points Q0 + Q1 of each message's hash before its
          cofactor is cleared, coords (N, 2, 25)
    wbits:(N, 64) int32, MSB-first random weights (zero rows for padding)
    live: (N,) bool
    rows: None, or (N, RB) int32: the key rows of each set, -1 past its last
    Returns (fe, w_z): final-exponentiation output (12-coeff limbs) and the
    Z coordinate of W = sum_i [r_i] sig_i for the host-side infinity check.

    The stages carry named scopes (HLO metadata only), which
    ``device_telemetry.program_stages`` maps the compiled instructions to:
    s1-s3 and ``h2c_clear`` here (``s1_set_sum`` only where ``rows`` is
    given), ``miller_loop`` and ``final_exp`` (s4, s5) in the pairing.
    """
    with jax.named_scope("s1_key_sum"):
        agg = ec.tree_sum(ec.G1_OPS, pk, axis=1)              # (R,) G1 proj
    if rows is not None:
        with jax.named_scope("s1_set_sum"):
            agg = _set_sums(agg, rows)                        # (N,) G1 proj
    # [r_i] aggpk_i stays per-set (each feeds its own pairing); windowed
    # ladder instead of double-and-add (VERDICT r3 item 2).
    with jax.named_scope("s2_g1_ladder"):
        p_weighted = ec.scalar_mul_windowed(ec.G1_OPS, agg, wbits)
    # W = sum_i [r_i] sig_i is ONE multi-scalar multiplication — the shared
    # windowed ladder does ~4x fewer G2 group ops than per-set ladders
    # followed by a tree-sum (blst.rs:112-114 computes this same sum).
    with jax.named_scope("s3_g2_msm"):
        w = ec.msm_windowed(ec.G2_OPS, sig, wbits)            # G2 proj
        # W -> affine (zero-divides yield exact 0 limbs, caught by the host
        # check).
        zi = tower.fq2_inv(w[2])
        w_aff = (tower.fq2_mul(w[0], zi), tower.fq2_mul(w[1], zi))
    with jax.named_scope("h2c_clear"):
        h_aff = _h2c_clear(msg)

    # Assemble N+1 pairs: ( [r_i]aggpk_i, H_i ) ... ( -g1, W ).
    def cat(a, b):
        return jnp.concatenate([a, b[None]], axis=0)

    p1 = tuple(cat(p_weighted[i], jnp.asarray(_NEG_G1[i])) for i in range(3))
    q2 = tuple(cat(h_aff[i], w_aff[i]) for i in range(2))
    mask = jnp.concatenate([live, jnp.asarray([True])])
    fe = pairing.multi_pairing_fe(p1, q2, mask)
    return fe, w[2]


#: The named scopes of ``_device_verify``'s stages, in program order.
STAGES = ("s1_key_sum", "s1_set_sum", "s2_g1_ladder", "s3_g2_msm",
          "h2c_clear", "miller_loop", "final_exp")


def bucket_shape(batch) -> Tuple[int, ...]:
    """The bucket a host or placed batch was built at: ``(nb, kb)``, or
    ``(nb, kb, nr, rb)`` where sets span key rows (``nr`` key rows of width
    ``kb``, up to ``rb`` of them a set)."""
    pk, live, rows = batch[0], batch[4], batch[5]
    nb, kb = int(live.shape[0]), int(pk[0].shape[1])
    if rows is None:
        return nb, kb
    return nb, kb, int(pk[0].shape[0]), int(rows.shape[1])


ENTRY_KEY = "lighthouse_tpu/ops/verify.py:_device_verify"

#: ``_device_verify`` behind the dispatch seam.  Its pad rows are exact
#: neutral elements (identity points, generator hash slots, zero weights,
#: dead ``live`` rows, no key rows); key rows pad to their own mesh multiple.
PROGRAM = dispatch.Program(
    _device_verify,
    fills=(ec.g1_to_limbs(None), ec.g2_to_limbs(None), _G2_GEN_AFF, 0, False,
           -1),
    shape=bucket_shape,
    spans="device_batch",
)


def lower_for_stages(shape: Tuple[int, ...]):
    """``_device_verify`` lowered at the bucket ``shape`` (see
    :func:`bucket_shape`) with the dispatched arguments' types, so jit's
    caches hand back the executable the dispatches ran
    (``device_telemetry.program_stages``)."""
    from .compile_cache import _bucket_shape_structs

    return _device_verify.lower(*_bucket_shape_structs(*shape))


# --------------------------------------------------------------- host driver

_hash_cache: dict = {}


def _hash_to_g2_uncleared_cached(messages) -> Tuple[list, int, int]:
    """Each message's ``hash_to_g2_uncleared`` behind a bounded cache: the
    distinct misses, in order, hashed in one ``hash_to_g2_uncleared_many``
    call.  Returns the points, the number of misses (the marshal span's
    ``n_hash_misses``) and the modular exponentiations they cost
    (``modexp``)."""
    keys = [bytes(m) for m in messages]
    got = {key: _hash_cache.get(key) for key in keys}
    misses = [key for key, pt in got.items() if pt is None]
    pts, modexp = hash_to_g2_uncleared_many(misses, DST)
    got.update(zip(misses, pts))
    if len(_hash_cache) + len(misses) > 4096:
        _hash_cache.clear()
    _hash_cache.update(zip(misses, pts))
    return [got[key] for key in keys], len(misses), modexp


#: Host key arrays kept for the next batch of the same (rows, width): a
#: block's three (rows, width, 25) limb arrays are tens of MB, and fresh
#: pages fault in as the pack writes them, where recycled ones do not.  One
#: set per shape, at most ``_KEY_ARRAYS_MAX_BYTES`` in all, the least
#: recently returned dropped first.
_KEY_ARRAYS: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_KEY_ARRAYS_MAX_BYTES = 256 << 20
_key_arrays_lock = threading.Lock()


def _key_arrays(nr: int, kb: int) -> list:
    """Zeroed (nr, kb, 25) int32 arrays for x, y and z: recycled where a
    finished batch returned a set of this shape, else new."""
    with _key_arrays_lock:
        pk = _KEY_ARRAYS.pop((nr, kb), None)
    if pk is None:
        return [np.zeros((nr, kb, 25), np.int32) for _ in range(3)]
    for a in pk:
        a.fill(0)
    return list(pk)


def _return_key_arrays(pk) -> None:
    """Keep a finished batch's key arrays for the next batch of their shape."""
    nbytes = sum(a.nbytes for a in pk)
    if nbytes > _KEY_ARRAYS_MAX_BYTES:
        return
    key = pk[0].shape[:2]
    with _key_arrays_lock:
        _KEY_ARRAYS[key] = tuple(pk)
        _KEY_ARRAYS.move_to_end(key)
        total = sum(a.nbytes for v in _KEY_ARRAYS.values() for a in v)
        while total > _KEY_ARRAYS_MAX_BYTES:
            _, old = _KEY_ARRAYS.popitem(last=False)
            total -= sum(a.nbytes for a in old)


def build_batch(sets, rands) -> Optional[tuple]:
    """Validate + marshal signature sets into padded HOST arrays (numpy,
    bucket-shaped): ``(pk, sig, msg, wbits, live, rows)``, the arguments of
    ``_device_verify``.  Placement — single-device or mesh-sharded — is
    ``dispatch.Placed``; jit accepts the numpy arrays directly, so callers
    that dispatch these straight into ``_device_verify`` still work.

    Set i's keys fill key row i, and ``rows`` is None, while every set fits
    the top key bucket.  Past it, every row is the top bucket's width
    ``K_BUCKETS[-1]``, set i takes ``ceil(w_i / K_BUCKETS[-1])`` consecutive
    rows and ``rows[i]`` lists them (-1 past its last): no row is padded to
    the widest set's width.  The row count and the rows a set takes bucket
    against ``N_BUCKETS``.

    Returns None if host-side validation already decides False.

    Three contiguous phases, each a span under the caller's
    ``device_batch_setup``: every signature's checks before any hashing
    (``marshal_subgroup``), every message's hash to E2 (``marshal_hash_to_g2``),
    then the limbs of all sets (``marshal_pack``).
    """
    n = len(sets)
    # The set axis buckets against the LIVE vocabulary (static N_BUCKETS
    # plus any controller-adopted overlay buckets); the key axis stays
    # static — padding waste there is bounded by the committee shape.
    nb = _bucket(n, autotune.bucket_vocabulary("bls_verify", N_BUCKETS))
    counts = np.fromiter((len(s.signing_keys) for s in sets), np.intp, n)
    spans = int(counts.max()) > K_BUCKETS[-1]
    kb = K_BUCKETS[-1] if spans else _bucket(int(counts.max()), K_BUCKETS)
    set_rows = np.maximum(1, -(-counts // kb))  # key rows each set takes
    n_rows = int(set_rows.sum())
    nr = _bucket(n_rows, N_BUCKETS) if spans else nb

    with tracing.span("marshal_subgroup", n_sets=n):
        for s in sets:
            sig_pt = s.signature.point
            if sig_pt is None or not curve.in_g2(sig_pt):
                return None
            if not s.signing_keys:
                return None

    with tracing.span("marshal_hash_to_g2", n_messages=n,
                      cleared_on_device=n) as sp_hash:
        hashes, n_misses, modexp = _hash_to_g2_uncleared_cached(
            s.message for s in sets)
        sp_hash.fields["n_hash_misses"] = n_misses
        sp_hash.fields["modexp"] = modexp

    n_keys = int(counts.sum())
    with tracing.span("marshal_pack", n_keys=n_keys, n_rows=n_rows, k_row=kb):
        pk = _key_arrays(nr, kb)
        sig = [np.zeros((nb, 2, 25), np.int32) for _ in range(3)]
        msg = [np.zeros((nb, 2, 25), np.int32) for _ in range(2)]
        wbits = np.zeros((nb, 64), np.int32)
        live = np.zeros((nb,), bool)

        # Identity padding (0, 1, 0): the zeros already hold x and z.
        id1 = ec.g1_to_limbs(None)
        id2 = ec.g2_to_limbs(None)
        pk[1][:] = id1[1]
        for c in range(3):
            sig[c][:] = id2[c]
        for c in range(2):
            msg[c][:] = _G2_GEN_AFF[c]

        # Key j of set i goes to row first[i] + j // kb, column j % kb: one
        # scatter for all keys.
        first = np.cumsum(set_rows) - set_rows
        j = np.arange(n_keys) - np.repeat(np.cumsum(counts) - counts, counts)
        krow = np.repeat(first, counts) + j // kb
        kcol = j % kb
        kx, ky = ec.g1_batch_to_limbs(
            [key.point for s in sets for key in s.signing_keys])
        pk[0][krow, kcol] = kx
        pk[1][krow, kcol] = ky
        pk[2][krow, kcol] = id1[1]  # z = 1: the limbs of one

        rows = None
        if spans:
            rb = _bucket(int(set_rows.max()), N_BUCKETS)
            r = np.arange(rb)
            rows = np.full((nb, rb), -1, np.int32)
            rows[:n] = np.where(r < set_rows[:, None], first[:, None] + r, -1)

        sig[0][:n], sig[1][:n] = ec.g2_batch_to_limbs(
            [s.signature.point for s in sets])
        sig[2][:n] = id2[1]  # z = 1
        msg[0][:n], msg[1][:n] = ec.g2_batch_to_limbs(hashes)
        wbits[:n] = ec.bits_msb_batch(rands[:n], 64)
        live[:n] = True

    return tuple(pk), tuple(sig), tuple(msg), wbits, live, rows


def _verdict(out) -> bool:
    """The verdict of one dispatched batch.  W at infinity poisons the
    Miller value: the device disclaims it, and the supervisor decides on
    the host through the one shared fallback path."""
    fe, w_z = out
    if tower.fq2_from_limbs(np.asarray(w_z)).is_zero():
        tracing.annotate(host_fallback=True)
        raise device_supervisor.HostFallback("w_at_infinity")
    return pairing.fe_is_one(fe)


class BuiltBatch(dispatch.Placed):
    """A marshalled batch between the build and dispatch stages.

    The two stages are separately callable so the async device pipeline
    (``device_pipeline.py``) can overlap host-side building of batch N+1
    (its builder thread calls :func:`build_device_batch`) with the in-flight
    device execution of batch N (its executor thread calls
    :func:`execute_built_batch`).  ``verify_signature_sets_device`` is the
    two stages run back-to-back — the direct, non-pipelined path."""

    __slots__ = ("sets", "seed", "live_keys")

    def __init__(self, sets, seed, host_batch):
        self.sets = sets
        self.seed = seed
        self.live_keys = sum(len(s.signing_keys) for s in sets)
        with tracing.span("marshal_upload"):
            super().__init__(PROGRAM, host_batch)

    def release(self) -> None:
        """Return the host key arrays for the next batch of their shape,
        once the device is done with this one; the batch cannot be placed
        again."""
        host = self.host
        super().release()
        if host is not None:
            _return_key_arrays(host[0])


def build_device_batch(sets, seed: Optional[bytes] = None) -> Optional[BuiltBatch]:
    """Stage 1 — host-side marshalling (validation, hash-to-curve, limb
    packing; the ``marshal_*`` spans of :func:`build_batch`, then
    ``marshal_upload``) into padded device arrays.  Returns None when host-side
    validation already decides False (bad/missing signature, empty key
    list).  Safe to call from any thread; no device work happens here
    beyond the host→device array uploads."""
    sets = list(sets)
    if not sets or len(sets) > MAX_SETS_PER_DISPATCH:
        raise ValueError(
            f"build_device_batch takes 1..{MAX_SETS_PER_DISPATCH} sets, "
            f"got {len(sets)}"
        )
    with tracing.span(
        "device_batch_setup", hist=metrics.DEVICE_BATCH_SETUP_SECONDS,
        n_sets=len(sets),
    ) as sp_setup:
        host_batch = build_batch(sets, _rand_scalars(len(sets), seed))
        if host_batch is None:
            return None
        # The upload is part of the build stage, so the pipeline overlaps
        # it with the in-flight batch like the rest of setup.
        built = BuiltBatch(sets, seed, host_batch)
    built.setup_s = sp_setup.duration
    return built


def execute_built_batch(built: BuiltBatch, *, n_groups: int = 1,
                        work_mix: Optional[dict] = None) -> bool:
    """Stage 2 — supervised dispatch + wait + verdict for a built batch
    (``ops/dispatch.py``: watchdog, one split-batch retry at the halves'
    own buckets, the circuit breaker routing to the host golden model, the
    flight record).  ``n_groups``/``work_mix`` attribute a
    pipeline-coalesced batch's composition in the flight record."""
    from ..crypto.bls.backends import host

    sets, seed = built.sets, built.seed
    return PROGRAM.run(
        built,
        n_live=len(sets),
        host_fn=lambda: host.verify_signature_sets(sets, seed=seed),
        verdict=_verdict,
        half=lambda lo, hi: build_batch(sets[lo:hi],
                                        _rand_scalars(hi - lo, seed)),
        combine=all,
        live_keys=built.live_keys,
        n_groups=n_groups,
        work_mix=work_mix,
    )


def verify_signature_sets_device(sets, seed: Optional[bytes] = None) -> bool:
    """Drop-in batch verifier running the hot path on the JAX backend — the
    build and dispatch stages run back-to-back on the calling thread.

    Instrumented per stage (setup / dispatch / block-until-ready / verdict —
    reference metrics.rs:247-271): the dispatch timer measures only the
    async enqueue; the block-until-ready timer is the device execution
    window a TPU perf investigation cares about.  Each stage span feeds its
    histogram AND the active trace (tracing.py), with batch-size and bucket
    fields, so a slow batch inside a block import is attributable.

    Device telemetry (device_telemetry.py) rides the same seams: the
    dispatch duration of a first-seen (nb, kb) registers in the compile
    cache, occupancy is accounted against the padded shape, and the whole
    batch lands in the flight recorder linked to the active trace id.

    Execution is supervised (device_supervisor.py): the device leg runs
    under a dispatch-deadline watchdog, transient device errors get one
    split-batch retry, and a per-op circuit breaker routes batches to the
    host golden model while the device is failing — so a device fault
    degrades the chain to slow-but-correct instead of crashing it."""
    sets = list(sets)
    if not sets:
        return False
    if len(sets) > MAX_SETS_PER_DISPATCH:
        # Oversized batches chunk through the standard top bucket: each
        # chunk is an independently supervised dispatch (split-retry and
        # breaker semantics per chunk), verdicts AND together.  The seed is
        # shared — each chunk is its own batch-verification equation, so
        # repeated blinding weights across chunks are harmless.
        return all(
            verify_signature_sets_device(
                sets[i:i + MAX_SETS_PER_DISPATCH], seed=seed
            )
            for i in range(0, len(sets), MAX_SETS_PER_DISPATCH)
        )
    built = build_device_batch(sets, seed=seed)
    if built is None:
        return False
    return execute_built_batch(built)
