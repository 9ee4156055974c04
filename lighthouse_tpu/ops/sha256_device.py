"""Batched SHA-256 as a JAX device kernel.

The reference's second crypto hot loop after BLS is SHA-256 Merkleization
(``ethereum_hashing`` with asm/SIMD backends — SURVEY §2.1: "a TPU build
wants a vectorized hash").  This kernel hashes N independent 64-byte blocks
(exactly the Merkle pair-hash shape) as pure uint32 array ops: the message
schedule and 64 compression rounds vectorize over the batch axis, so XLA
maps the whole layer onto the VPU with no per-hash control flow.

Shape-bucketed and jitted per bucket like the pairing program; the host
fallback (`native/hash_pairs.cc` SHA-NI) stays the default for small layers
where dispatch overhead dominates — ``hash_pairs_device`` is the drop-in
``set_hash_pairs_impl`` backend for bulk tree builds.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .. import autotune
from . import dispatch

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)

# Constant padding block for an exactly-64-byte message: 0x80 then zeros,
# 512-bit length in the final word.
_PAD_WORDS = np.zeros(16, dtype=np.uint32)
_PAD_WORDS[0] = 0x80000000
_PAD_WORDS[15] = 512

N_BUCKETS = (256, 1024, 4096, 16384, 65536, 262144)


def _aot_warmup(nb: int) -> None:
    from .compile_cache import aot_warmup_op

    aot_warmup_op("sha256_pairs", nb)


# Self-tuning enrolment (autotune.py): this ratio-4 vocabulary has real
# gaps, so the controller's densify heuristic can overlay midpoint buckets
# (e.g. 640 between 256 and 1024) when the flight recorder shows the
# median dispatched layer wasting over half its lanes.  N_BUCKETS stays
# the floor and its top bucket the device-size ceiling; every adoption is
# gated on a committed hlo_budget entry plus off-path AOT warmup.
autotune.register_vocabulary(
    "sha256_pairs", N_BUCKETS,
    telemetry_ops=("sha256_pairs",),
    budget_key=lambda nb: f"sha256_pairs|-|{nb}|-",
    warmup=_aot_warmup,
)


def _rotr(x, n):
    return (x >> n) | (x << (32 - n))


def _compress(state, w_block):
    """One compression over a (N, 16)-word block; ``state`` is (N, 8) u32.

    Both the message-schedule expansion and the 64 rounds run as
    ``lax.fori_loop``s: a fully unrolled graph (64×~12 ops on the batch
    axis) sends XLA's algebraic simplifier into a pathological
    multi-minute loop; the rolled form compiles in seconds and the
    per-iteration body still vectorizes over the batch."""
    n = w_block.shape[0]
    k = jnp.asarray(_K, dtype=jnp.uint32)

    # Schedule: ring buffer of the last 16 words, emitting w[i] per round.
    def round_body(i, carry):
        ring, state = carry
        a, b, c, d, e, f, g, hh = [state[:, j] for j in range(8)]
        wi = ring[:, 0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = hh + s1 + ch + k[i] + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        new_state = jnp.stack(
            [t1 + t2, a, b, c, d + t1, e, f, g], axis=1
        )
        # extend the schedule: w[i+16] from the ring's positions 0,1,9,14
        w0, w1, w9, w14 = ring[:, 0], ring[:, 1], ring[:, 9], ring[:, 14]
        sig0 = _rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> 3)
        sig1 = _rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> 10)
        w_next = w0 + sig0 + w9 + sig1
        ring = jnp.concatenate([ring[:, 1:], w_next[:, None]], axis=1)
        return ring, new_state

    ring0 = w_block
    _, out = jax.lax.fori_loop(0, 64, round_body, (ring0, state))
    return state + out


@functools.partial(jax.jit, static_argnums=())
def _sha256_64byte_batch(words):
    """words: (N, 16) uint32 big-endian message words -> (N, 8) uint32."""
    n = words.shape[0]
    state = jnp.broadcast_to(
        jnp.asarray(_H0, dtype=jnp.uint32), (n, 8)
    ).astype(jnp.uint32)
    state = _compress(state, words)
    pad = jnp.broadcast_to(jnp.asarray(_PAD_WORDS, dtype=jnp.uint32), (n, 16))
    state = _compress(state, pad)
    return state


#: The pair-hash kernel behind the dispatch seam: every 64-byte block is
#: independent, so a mesh splits the blocks with no collectives.
PROGRAM = dispatch.Program(_sha256_64byte_batch, fills=(0,))


def _bucket(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    if buckets is None:
        # the live vocabulary: static N_BUCKETS plus controller-adopted
        # overlay buckets (autotune.py) — identical to N_BUCKETS when the
        # controller is off or has adopted nothing
        buckets = autotune.bucket_vocabulary("sha256_pairs", N_BUCKETS)
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} blocks exceeds max bucket {buckets[-1]}")


_installed = False

# The true host kernel, captured before install_device_hash swaps the ssz
# seam — the supervisor's fallback must reach the native/hashlib impl, not
# recurse into the installed hybrid wrapper.
_HOST_IMPL = None


def install_device_hash(threshold_blocks: int = 8192) -> None:
    """Install a hybrid pair-hash kernel: device for layers of
    ``threshold_blocks``+ (where a TPU's VPU amortizes dispatch), the
    existing host kernel (SHA-NI native / hashlib) below it.  Opt-in via
    ``LIGHTHOUSE_TPU_DEVICE_SHA=1`` at node assembly.  Idempotent — building
    several clients in one process (the simulator) must not stack wrappers."""
    global _installed, _HOST_IMPL
    if _installed:
        return
    from ..types import ssz as ssz_mod

    host_impl = ssz_mod._hash_pairs
    _HOST_IMPL = host_impl

    def hybrid(data: bytes) -> bytes:
        n = len(data) // 64
        if threshold_blocks <= n <= N_BUCKETS[-1]:
            return hash_pairs_device(data)
        # below threshold OR above the largest bucket: the host kernel
        # (oversize layers must never crash hash_tree_root)
        return host_impl(data)

    ssz_mod.set_hash_pairs_impl(hybrid)
    _installed = True


def _host_hash_pairs(data: bytes) -> bytes:
    """The host kernel (SHA-NI native / hashlib) as the supervisor's
    fallback.  Uses the impl captured before :func:`install_device_hash`
    swapped the ssz seam — never the installed hybrid (which would recurse
    right back into the device path)."""
    if _HOST_IMPL is not None:
        return _HOST_IMPL(data)
    from ..types import ssz as ssz_mod

    return ssz_mod._hash_pairs(data)


def _words(data: bytes, n: int) -> np.ndarray:
    """``n`` 64-byte blocks as big-endian words, padded to their bucket."""
    buf = np.zeros((_bucket(n), 64), dtype=np.uint8)
    buf[:n] = np.frombuffer(data[: n * 64], dtype=np.uint8).reshape(n, 64)
    return buf.view(">u4").astype(np.uint32)


def _digests(out: np.ndarray, n: int) -> bytes:
    return out[:n].astype(">u4").tobytes()


def hash_pairs_device(data: bytes) -> bytes:
    """Drop-in for ``types.ssz.set_hash_pairs_impl``: hash consecutive
    64-byte blocks on the device (padded to a shape bucket so every layer
    size reuses a cached executable).  Telemetry: the dispatch registers in
    the compile-cache mirror and the batch's block-lane occupancy is
    accounted (device_telemetry.py) — all host-side, outside the jit.

    Supervised (device_supervisor.py): a hung or failing device batch
    resolves through the host SHA kernel, split-retried once first — each
    64-byte block is independent, so halves concatenate exactly."""
    n = len(data) // 64
    if n == 0:
        return b""
    words = _words(data, n)
    return PROGRAM.run(
        lambda: dispatch.Placed(PROGRAM, (words,)),
        n_live=n,
        host_fn=lambda: _host_hash_pairs(data),
        finish=_digests,
        half=lambda lo, hi: (_words(data[lo * 64:], hi - lo),),
        combine=b"".join,
        bucket=(words.shape[0],),
    )
