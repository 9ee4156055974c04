"""Incremental device tree-hashing: Merkleization as batched SHA-256 work.

The reference spends a whole subsystem on exactly this
(``consensus/cached_tree_hash`` + milhouse's tree-backed ``BeaconState``):
at mainnet shape (~1M validators) state Merkleization is the hot path right
after BLS, and the winning strategy is *incremental* — keep the interior
Merkle nodes, re-hash only the ancestor paths of leaves that actually
changed.  This module is that blueprint on the device stack:

- :func:`_tree_hash_subtrees` — a fused jitted program that Merkleizes a
  batch of depth-:data:`SUBTREE_DEPTH` subtrees (32 leaf chunks each) in
  ONE dispatch, returning every interior level.  Full (re)builds of a big
  field walk the tree ``SUBTREE_DEPTH`` levels per dispatch instead of one
  pair-hash round trip per level — log32 dispatches for a registry, not
  log2.  Batched over the subtree axis, bucketed (:data:`N_BUCKETS`),
  mesh-shardable (``ops/batch_axes.py`` entry), supervised through
  ``ops/dispatch.py`` (watchdog, split-retry, breaker → the hashlib host
  model).
- :class:`DeviceLeafTree` — the cached-tree-hash layer: leaf chunks and all
  interior levels stay HOST-side as numpy arrays; ``update`` diffs the new
  leaves against the cache with one vectorized compare and re-hashes only
  dirty paths, each level's changed pairs as one ``sha256_pairs`` batch
  (pipeline-coalesced via :func:`hash_pairs` when the async device pipeline
  is on) — cost scales with dirty leaves, not registry size.  Structure-
  compatible with ``types/tree_cache._LeafTree`` so the state cache can
  swap engines per field.
- :func:`hash_pairs` — THE pair-hash seam for tree-hash traffic: layers big
  enough to amortize a dispatch ride the device (coalesced through the
  ``sha256_pairs`` hash pipeline when enabled, the supervised direct op
  otherwise); everything below the thresholds stays on the host kernel.

Every path is bit-identical to the pure-hashlib golden model
(:func:`golden_root`); tests/test_tree_hash.py asserts exact parity through
arbitrary mutations, size changes and fault injection.
"""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import dispatch
from .sha256_device import _H0, _K, _PAD_WORDS

#: Depth of the fused subtree program: 32 leaf chunks -> 1 root per subtree,
#: all five interior levels returned (the host cache needs every node).
SUBTREE_DEPTH = 5
SUBTREE_LEAVES = 1 << SUBTREE_DEPTH

#: Subtree-count buckets: the top bucket (32768 subtrees) Merkleizes one
#: 2^20-chunk level — the mainnet validator registry — in a single
#: dispatch.  Bigger levels chunk through the top bucket.
N_BUCKETS = (8, 128, 2048, 32768)

#: Precomputed zero-subtree roots (index d = root of a depth-d all-zero
#: tree) — the right-edge padding vocabulary, identical to types/ssz.py's
#: table (recomputed here so ops/ stays import-light).
import hashlib as _hashlib

ZERO_HASHES: List[bytes] = [b"\x00" * 32]
for _ in range(64):
    ZERO_HASHES.append(
        _hashlib.sha256(ZERO_HASHES[-1] + ZERO_HASHES[-1]).digest()
    )


# ------------------------------------------------------------ configuration

_ENABLED = os.environ.get("LIGHTHOUSE_TPU_DEVICE_TREE_HASH", "") == "1"

#: A full-rebuild level smaller than this many subtrees stays on the host
#: kernel (dispatch overhead dominates tiny trees).
_DEVICE_MIN_SUBTREES = int(
    os.environ.get("LIGHTHOUSE_TPU_TREE_HASH_MIN_SUBTREES", "4")
)

#: A dirty-path pair batch smaller than this many 64-byte blocks stays on
#: the host kernel; at or above it the batch rides :func:`hash_pairs`'
#: device route (pipeline-coalesced sha256_pairs when enabled).
_DEVICE_MIN_BLOCKS = int(
    os.environ.get("LIGHTHOUSE_TPU_TREE_HASH_MIN_BLOCKS", "64")
)


def enabled() -> bool:
    return _ENABLED


def configure(enabled: Optional[bool] = None,
              device_min_subtrees: Optional[int] = None,
              device_min_blocks: Optional[int] = None) -> None:
    """Re-tune the device routing (tests / scenario events / ClientBuilder).
    ``enabled=False`` keeps every path on the host kernel — the default on
    CPU-only nodes, where hashlib/SHA-NI beats a jax round trip."""
    global _ENABLED, _DEVICE_MIN_SUBTREES, _DEVICE_MIN_BLOCKS
    if enabled is not None:
        _ENABLED = bool(enabled)
    if device_min_subtrees is not None:
        _DEVICE_MIN_SUBTREES = max(1, int(device_min_subtrees))
    if device_min_blocks is not None:
        _DEVICE_MIN_BLOCKS = max(1, int(device_min_blocks))


def reset_for_tests() -> None:
    configure(
        enabled=os.environ.get("LIGHTHOUSE_TPU_DEVICE_TREE_HASH", "") == "1",
        device_min_subtrees=int(
            os.environ.get("LIGHTHOUSE_TPU_TREE_HASH_MIN_SUBTREES", "4")),
        device_min_blocks=int(
            os.environ.get("LIGHTHOUSE_TPU_TREE_HASH_MIN_BLOCKS", "64")),
    )


# -------------------------------------------------------------- the kernel


def _rotr(x, n):
    return (x >> n) | (x << (32 - n))


def _compress_nd(state, w_block):
    """One SHA-256 compression over ``(..., 16)``-word blocks;
    ``state`` is ``(..., 8)`` uint32.  The nd generalization of
    ``sha256_device._compress`` (same rolled 64-round ``fori_loop`` — the
    unrolled graph sends XLA's simplifier into a multi-minute loop)."""
    k = jnp.asarray(_K, dtype=jnp.uint32)

    def round_body(i, carry):
        ring, st = carry
        a, b, c, d, e, f, g, hh = [st[..., j] for j in range(8)]
        wi = ring[..., 0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = hh + s1 + ch + k[i] + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        new_state = jnp.stack(
            [t1 + t2, a, b, c, d + t1, e, f, g], axis=-1
        )
        w0, w1, w9, w14 = (ring[..., 0], ring[..., 1],
                           ring[..., 9], ring[..., 14])
        sig0 = _rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> 3)
        sig1 = _rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> 10)
        w_next = w0 + sig0 + w9 + sig1
        ring = jnp.concatenate([ring[..., 1:], w_next[..., None]], axis=-1)
        return ring, new_state

    _, out = jax.lax.fori_loop(0, 64, round_body, (w_block, state))
    return state + out


def _hash_pair_level(nodes):
    """``(m, w, 8)`` u32 nodes -> ``(m, w//2, 8)``: SHA-256 of each
    adjacent 32-byte pair (exactly-64-byte message: data block + constant
    padding block)."""
    m, w = nodes.shape[0], nodes.shape[1]
    blocks = nodes.reshape(m, w // 2, 16)
    state = jnp.broadcast_to(
        jnp.asarray(_H0, dtype=jnp.uint32), (m, w // 2, 8)
    ).astype(jnp.uint32)
    state = _compress_nd(state, blocks)
    pad = jnp.broadcast_to(
        jnp.asarray(_PAD_WORDS, dtype=jnp.uint32), (m, w // 2, 16)
    )
    return _compress_nd(state, pad)


@jax.jit
def _tree_hash_subtrees(leaves):
    """Merkleize a batch of 32-leaf subtrees in one fused program.

    leaves: (m, 32, 8) uint32 big-endian words of 32-byte leaf chunks.
    Returns the 5 interior levels, per subtree:
    ((m, 16, 8), (m, 8, 8), (m, 4, 8), (m, 2, 8), (m, 1, 8)).
    """
    levels = []
    level = leaves
    for _ in range(SUBTREE_DEPTH):
        level = _hash_pair_level(level)
        levels.append(level)
    return tuple(levels)


#: The subtree kernel behind the dispatch seam: every subtree is
#: independent, so a mesh splits the subtree axis with no collectives.
PROGRAM = dispatch.Program(_tree_hash_subtrees, fills=(0,))


# -------------------------------------------------------------- host driver


def _bucket(m: int) -> int:
    for b in N_BUCKETS:
        if m <= b:
            return b
    raise ValueError(f"batch of {m} subtrees exceeds max bucket {N_BUCKETS[-1]}")


def _chunks_to_words(chunks: np.ndarray) -> np.ndarray:
    """(n, 32) uint8 chunks (n a multiple of 32) -> (m, 32, 8) uint32 BE."""
    m = chunks.shape[0] // SUBTREE_LEAVES
    return np.ascontiguousarray(
        chunks.reshape(m, SUBTREE_LEAVES, 32)
    ).view(">u4").astype(np.uint32)


def _words_to_chunks(words: np.ndarray) -> np.ndarray:
    """(m, w, 8) uint32 -> (m*w, 32) uint8."""
    m, w = words.shape[0], words.shape[1]
    return np.frombuffer(
        np.ascontiguousarray(words).astype(">u4").tobytes(), dtype=np.uint8
    ).reshape(m * w, 32)


def golden_hash_pairs(data: bytes) -> bytes:
    """The pure-hashlib pair hash — the golden model every device path must
    match bit-for-bit (and the supervisor's terminal host fallback)."""
    out = bytearray()
    for i in range(0, len(data), 64):
        out += _hashlib.sha256(data[i: i + 64]).digest()
    return bytes(out)


def _host_subtree_levels(words: np.ndarray) -> List[np.ndarray]:
    """The hashlib golden model of :func:`_tree_hash_subtrees`: same input
    words, same 5 per-subtree levels, pure host."""
    m = words.shape[0]
    level = _words_to_chunks(words)  # (m*32, 32) u8
    out = []
    for d in range(SUBTREE_DEPTH):
        hashed = golden_hash_pairs(level.reshape(-1, 64).tobytes())
        level = np.frombuffer(hashed, dtype=np.uint8).reshape(-1, 32)
        w = SUBTREE_LEAVES >> (d + 1)
        out.append(
            np.ascontiguousarray(level.reshape(m, w, 32)
                                 ).view(">u4").astype(np.uint32)
        )
    return out


def _padded(words: np.ndarray) -> np.ndarray:
    """Subtree words padded with zero subtrees to their bucket."""
    m = words.shape[0]
    mb = _bucket(m)
    if mb == m:
        return words
    padded = np.zeros((mb,) + words.shape[1:], dtype=np.uint32)
    padded[:m] = words
    return padded


def hash_subtree_levels(chunks: np.ndarray) -> List[np.ndarray]:
    """Merkleize one level of 32-byte ``chunks`` (shape ``(n, 32)`` uint8,
    ``n`` a positive multiple of :data:`SUBTREE_LEAVES`) through the fused
    device program, :data:`SUBTREE_DEPTH` levels at once.

    Returns the 5 interior levels as flat chunk arrays
    ``[(n/2, 32), (n/4, 32), ..., (n/32, 32)]`` uint8 — Merkle level order
    (each subtree's nodes are contiguous).  Supervised: a hung or failing
    dispatch resolves through the hashlib golden model, split-retried once
    first (subtrees are independent, halves concatenate exactly)."""
    n = int(chunks.shape[0])
    if n == 0 or n % SUBTREE_LEAVES:
        raise ValueError(f"level of {n} chunks is not a subtree multiple")
    m = n // SUBTREE_LEAVES
    top = N_BUCKETS[-1]
    if m > top:
        # Oversized levels chunk through the top bucket (independently
        # supervised dispatches; per-level outputs concatenate exactly).
        parts = [
            hash_subtree_levels(chunks[i * SUBTREE_LEAVES:
                                       (i + top) * SUBTREE_LEAVES])
            for i in range(0, m, top)
        ]
        return [np.concatenate(level) for level in zip(*parts)]

    words = _chunks_to_words(chunks)
    padded = _padded(words)
    out_words = PROGRAM.run(
        lambda: dispatch.Placed(PROGRAM, (padded,)),
        n_live=m,
        host_fn=lambda: _host_subtree_levels(words),
        finish=lambda out, k: [lv[:k] for lv in out],
        half=lambda lo, hi: (_padded(words[lo:hi]),),
        combine=lambda halves: [np.concatenate(lv) for lv in zip(*halves)],
        bucket=(padded.shape[0],),
    )
    return [_words_to_chunks(lv) for lv in out_words]


# ---------------------------------------------------------- pair-hash seam


def hash_pairs(data: bytes) -> bytes:
    """THE pair-hash seam for tree-hash traffic.

    Device tree hashing on + a layer big enough to amortize a dispatch:
    ride the async device pipeline's ``sha256_pairs`` hash pipeline (the
    batch coalesces with block-import and gossip hash traffic and contends
    for the device through the shared arbiter); pipeline off: the
    supervised direct device op.  Everything else — small layers, device
    hashing disabled — stays on the host kernel.  All routes are
    bit-identical (the device op's breaker/host fallback resolves through
    the golden model)."""
    n = len(data) // 64
    if n == 0:
        return b""
    from .sha256_device import N_BUCKETS as SHA_BUCKETS
    from .sha256_device import _host_hash_pairs, hash_pairs_device

    if _ENABLED and _DEVICE_MIN_BLOCKS <= n <= SHA_BUCKETS[-1]:
        from .. import device_pipeline

        if device_pipeline.routes_hash(n):
            try:
                return device_pipeline.hash_pairs(data)
            except device_pipeline.PipelineShutdown:
                pass  # racing shutdown: fall through to the direct path
        return hash_pairs_device(data)
    return _host_hash_pairs(data)


# ------------------------------------------------------- incremental cache


def _ceil_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def golden_root(leaves: np.ndarray, limit_chunks: int) -> bytes:
    """Pure-hashlib golden model: merkleize ``(n, 32)`` uint8 leaf chunks
    under a ``limit_chunks`` zero-subtree cap (the ssz ``merkleize``
    semantics, computed with nothing but hashlib)."""
    limit = max(1, int(limit_chunks))
    depth = max(0, (limit - 1).bit_length())
    n = len(leaves)
    if n > limit:
        raise ValueError(f"{n} chunks exceeds limit {limit}")
    if n == 0:
        return ZERO_HASHES[depth]
    layer = [leaves[i].tobytes() for i in range(n)]
    for d in range(depth):
        if len(layer) % 2:
            layer.append(ZERO_HASHES[d])
        layer = [
            _hashlib.sha256(layer[i] + layer[i + 1]).digest()
            for i in range(0, len(layer), 2)
        ]
    return layer[0]


class DeviceLeafTree:
    """Incremental Merkle tree over 32-byte leaf chunks, device-built.

    The cached-tree-hash layer: leaves and every interior level live
    host-side as ``(k, 32)`` uint8 numpy arrays covering the *occupied*
    prefix (everything right of it is the all-zero subtree, folded via
    :data:`ZERO_HASHES`).  ``update`` diffs new leaves against the cache in
    one vectorized compare; only the ancestor paths of changed leaves
    re-hash — each level's dirty pairs as ONE batch through
    :func:`hash_pairs` (pipeline-coalesced ``sha256_pairs`` for big dirty
    sets, host kernel for small ones).  A first build or occupied-size
    change rebuilds bottom-up through the fused subtree program
    (:func:`hash_subtree_levels`), :data:`SUBTREE_DEPTH` levels per
    dispatch.

    Attribute layout (``limit``/``depth``/``leaves``/``layers``/``_root``)
    is deliberately identical to ``types/tree_cache._LeafTree`` so the
    state cache's clone path handles either engine.
    """

    def __init__(self, limit_chunks: int):
        self.limit = limit_chunks
        self.depth = max(0, (limit_chunks - 1).bit_length())
        self.leaves: Optional[np.ndarray] = None  # (n, 32) uint8
        self.layers: List[np.ndarray] = []  # interior levels, bottom-up
        self._root: bytes = ZERO_HASHES[self.depth]

    # ------------------------------------------------------------- updates

    def update(self, new_leaves: np.ndarray,
               dirty_hint: Optional[np.ndarray] = None) -> bytes:
        """Bring the tree to ``new_leaves`` (shape (n, 32) uint8),
        re-hashing only changed paths; returns the root.

        ``dirty_hint`` — indices the CALLER asserts are the only possibly-
        changed leaves (milhouse's dirty-tracking model; the validator
        cache knows its dirty elements from the fingerprint diff).  Hinted
        rows are still diffed (a hint naming unchanged rows costs nothing),
        but un-hinted rows are TRUSTED unchanged — the O(n) full-leaf scan,
        which dominates a 1%-dirty re-hash at mainnet size, is skipped.  A
        WRONG hint (omitting a changed leaf) yields a stale root: only pass
        one from an exact source."""
        n = len(new_leaves)
        if n > self.limit:
            raise ValueError(f"{n} chunks exceeds limit {self.limit}")
        if self.leaves is None or len(self.leaves) != n:
            return self._rebuild(new_leaves)
        new_leaves = np.ascontiguousarray(new_leaves)
        if dirty_hint is not None:
            hint = np.unique(np.asarray(dirty_hint, dtype=np.int64))
            if hint.size == 0:
                return self._root
            changed = (
                self.leaves[hint].view(np.uint64)
                != new_leaves[hint].view(np.uint64)
            ).any(axis=1)
            dirty = hint[changed]
        else:
            # u64-view compare: 4 lanes/row beats the u8 row-any ~2x at
            # mainnet leaf counts (rows are 32 bytes, always 8-aligned)
            dirty = np.nonzero(
                (self.leaves.view(np.uint64)
                 != new_leaves.view(np.uint64)).any(axis=1)
            )[0]
        if dirty.size == 0:
            return self._root
        # scatter-copy only the changed rows: un-dirty rows are equal by
        # construction, and the full 33 MB copy was the second-largest cost
        # of a mainnet-size incremental update
        self.leaves[dirty] = new_leaves[dirty]
        level = self.leaves
        for d, layer in enumerate(self.layers):
            # ``dirty`` is sorted (nonzero/np.unique above, and parents of
            # sorted stay sorted), so dedup is one shifted compare — the
            # per-level np.unique sort was a measurable slice of a
            # mainnet-size 1%-dirty re-hash
            parents = dirty >> 1
            if parents.size > 1:
                keep = np.empty(parents.size, dtype=bool)
                keep[0] = True
                np.not_equal(parents[1:], parents[:-1], out=keep[1:])
                parents = parents[keep]
            lo = parents << 1
            hi = lo + 1
            pairs = np.empty((parents.size, 64), dtype=np.uint8)
            pairs[:, :32] = level[lo]
            # Right sibling may be past the occupied edge -> zero subtree.
            in_range = hi < len(level)
            pairs[in_range, 32:] = level[hi[in_range]]
            if not in_range.all():
                pairs[~in_range, 32:] = np.frombuffer(ZERO_HASHES[d],
                                                      dtype=np.uint8)
            hashed = hash_pairs(pairs.tobytes())
            layer[parents] = np.frombuffer(hashed, dtype=np.uint8
                                           ).reshape(-1, 32)
            dirty = parents
            level = layer
        self._root = self._fold_zero_cap(level)
        return self._root

    def _use_device(self, occupied: int) -> bool:
        return (_ENABLED
                and occupied >= _DEVICE_MIN_SUBTREES * SUBTREE_LEAVES)

    def _rebuild(self, new_leaves: np.ndarray) -> bytes:
        """Full bottom-up rebuild (first call, or occupied size changed):
        the fused subtree program walks :data:`SUBTREE_DEPTH` levels per
        dispatch while enough of the tree remains; the host kernel finishes
        the narrow top."""
        self.leaves = new_leaves.copy()
        self.layers = []
        level = self.leaves
        occupied_depth = max(
            0, (_ceil_pow2(max(len(level), 1)) - 1).bit_length())
        occupied_depth = min(occupied_depth, self.depth)
        d = 0
        while d < occupied_depth:
            if (occupied_depth - d >= SUBTREE_DEPTH
                    and self._use_device(len(level))):
                occ = len(level)
                pad_to = -(-occ // SUBTREE_LEAVES) * SUBTREE_LEAVES
                padded = level
                if pad_to != occ:
                    padded = np.empty((pad_to, 32), dtype=np.uint8)
                    padded[:occ] = level
                    padded[occ:] = np.frombuffer(ZERO_HASHES[d],
                                                 dtype=np.uint8)
                sub_levels = hash_subtree_levels(padded)
                for lv in sub_levels:
                    occ = -(-occ // 2)  # occupied width of the next level
                    layer = lv[:occ].copy()
                    self.layers.append(layer)
                    level = layer
                    d += 1
            else:
                if len(level) % 2:
                    zrow = np.frombuffer(ZERO_HASHES[d], dtype=np.uint8
                                         ).reshape(1, 32)
                    level = np.concatenate([level, zrow], axis=0)
                pairs = level.reshape(-1, 64)
                hashed = hash_pairs(pairs.tobytes())
                layer = np.frombuffer(hashed, dtype=np.uint8
                                      ).reshape(-1, 32).copy()
                self.layers.append(layer)
                level = layer
                d += 1
        self._root = self._fold_zero_cap(level)
        return self._root

    def _fold_zero_cap(self, top: np.ndarray) -> bytes:
        """Fold the top occupied level up to the limit depth with zero
        trees (identical to ``_LeafTree._fold_zero_cap``)."""
        d = len(self.layers)
        if len(top) == 0:
            return ZERO_HASHES[self.depth]
        root = top[0].tobytes()
        for level in range(d, self.depth):
            root = _hashlib.sha256(root + ZERO_HASHES[level]).digest()
        return root
