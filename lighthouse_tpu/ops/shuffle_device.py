"""Device (jnp) epoch-boundary consensus: swap-or-not shuffling, proposer
selection, and the fused whole-epoch dispatch.

The reference isolates ``swap_or_not_shuffle`` as a pure bit-twiddling
kernel (``consensus/swap_or_not_shuffle/src/shuffle_list.rs``) because it
dominates epoch-boundary CPU after signature work.  This module ports that
last O(validators) Python onto the device:

- :func:`_shuffle_kernel` — the whole-list swap-or-not network.  Per round
  the host precomputes one pivot plus the chunk digest row (via the same
  ``round_digest_table`` seam the numpy fast path uses, laid out flat so
  the byte covering ``position`` is ``digests[r, position >> 3]``), and the
  device applies the swap mask to every lane at once.  Round rows arrive
  host-reversed, so the kernel always walks its table forward.
- :func:`_proposer_kernel` — the spec's rejection-sampling candidate walk,
  vectorized over (slot, candidate) lanes.  The per-round source digest
  depends on each lane's current position, so it is hashed *on device*: the
  37-byte ``seed + round + chunk`` message fits one SHA-256 block, reusing
  ``sha256_device._compress``.  Acceptance (``eff * 255 >=
  max_eb * random_byte``) is evaluated for ``K`` candidates per slot; the
  rare slot that exhausts all ``K`` reports ``found=False`` and falls back
  to the scalar walk.
- :func:`_boundary_kernel` — the fused epoch boundary: the
  ``epoch_device._deltas_core`` pass, balance application, effective-balance
  hysteresis + registry-update masks (``_balance_core``), the next epoch's
  attester shuffling, and its per-slot proposer selection — ONE supervised,
  arbiter-slotted, mesh-shardable program per leak mode.  Committee slicing
  stays an O(1) host slice of the returned shuffling, per the
  ``shuffle_list``/``compute_shuffled_index`` invariant pinned in
  ``consensus/shuffling.py``.

Shape discipline matches ``ops/epoch_device.py``: power-of-two registry
buckets (:data:`N_BUCKETS`) with inert pad lanes — a pad lane never swaps
(``lane < n_live`` gate), is unreachable by the candidate walk (positions
stay below ``m_live``), and satisfies no registry-update mask.  Epoch math
needs 64-bit balances, so the proposer/boundary dispatches run under the
scoped ``jax.enable_x64`` context like the deltas pass.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from hashlib import sha256
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import autotune
from . import dispatch
from .epoch_device import _PAD_ACTIVATION_EPOCH, _balance_core, _deltas_core
from .sha256_device import _H0, _compress

#: Registry buckets — same ladder as the deltas pass (they dispatch over
#: the same registry axis and should promote at the same sizes).
N_BUCKETS = (64, 256, 1024, 4096, 16384, 65536, 262144, 1048576)

#: Candidates examined per slot by the device proposer walk.  Acceptance
#: probability per candidate is >= 1/32 even for minimum-balance registries
#: (worst case eff/max_eb = 1/32), so 64 candidates leave a not-found
#: probability below (31/32)^64 ~= 13% worst-case and ~1e-9 at mainnet
#: balances; a not-found slot simply stays on the scalar spec walk.
PROPOSER_CANDIDATES = 64

#: Per-pad-row fills for the boundary's batched argument tuple (eff_bal,
#: activation, exit, withdrawable, slashed, prev_part, inactivity,
#: balance, act_elig, eb_cap, active_idx): rows that are never active,
#: never eligible, never queueable, and carry no balance.
_PAD_FILLS = (0, _PAD_ACTIVATION_EPOCH, 0, 0, False, 0, 0, 0, 0, 1, 0)


def _chunk_count(nb: int) -> int:
    """Digest chunks covering every lane of an ``nb``-lane bucket: pad
    lanes index the table at ``lane >> 3`` too (their swap is masked off,
    but the gather must stay in bounds)."""
    return max(1, (nb + 255) // 256)


# --------------------------------------------------------------- kernels


def _shuffle_core(arr, pivots, digests, n_live):
    """Apply the swap-or-not rounds of ``pivots``/``digests`` (row 0 first)
    to every lane of ``arr``; lanes at or past ``n_live`` never swap."""
    nb = arr.shape[0]
    lane = jnp.arange(nb, dtype=jnp.int32)
    n_mod = jnp.maximum(n_live, 1).astype(jnp.int32)

    def round_body(r, a):
        flip = jnp.mod(pivots[r] - lane, n_mod)
        position = jnp.maximum(lane, flip)
        byte = digests[r, position >> 3]
        bit = (byte.astype(jnp.int32) >> (position & 7)) & 1
        swap = (bit == 1) & (lane < n_live)
        return jnp.where(swap, a[flip], a)

    return jax.lax.fori_loop(0, pivots.shape[0], round_body, arr)


@jax.jit
def _shuffle_kernel(values, pivots, digests, n_live):
    """values: (nb,) int32; pivots: (R,) int32 (list order — decreasing
    round, host-reversed); digests: (R, chunks*32) uint8; n_live: () int32.
    Returns the shuffled (nb,) array; pad lanes pass through untouched."""
    return _shuffle_core(values, pivots, digests, n_live)


def _proposer_core(seed_words, pivots, rbytes, eff_act, m_live, max_eb):
    """Vectorized spec ``compute_proposer_index`` walk.

    seed_words: (S, 8) uint32 — per-slot seed as big-endian SHA words;
    pivots: (S, R) int32 — per-slot round pivots (forward round order);
    rbytes: (S, K) int32 — the spec's acceptance random bytes;
    eff_act: (nb,) int64 — effective balance by *active-list position*;
    m_live: () int32 — live active count; max_eb: () int64.

    Returns ``(pos, found)``: per slot the accepted candidate's position in
    the active list (-1 when no candidate of the K accepted).
    """
    s, r_count = pivots.shape
    k = rbytes.shape[1]
    m_mod = jnp.maximum(m_live, 1).astype(jnp.int32)
    idx = jnp.broadcast_to(
        jnp.mod(jnp.arange(k, dtype=jnp.int32), m_mod), (s, k))
    h0 = jnp.broadcast_to(jnp.asarray(_H0, dtype=jnp.uint32), (s * k, 8))
    seed_b = jnp.broadcast_to(seed_words[:, None, :], (s, k, 8)).astype(
        jnp.uint32)
    zero_w = jnp.zeros((s, k), dtype=jnp.uint32)
    len_w = jnp.full((s, k), 296, dtype=jnp.uint32)  # 37 bytes = 296 bits

    def round_body(r, idx):
        flip = jnp.mod(pivots[:, r][:, None] + m_mod - idx, m_mod)
        position = jnp.maximum(idx, flip)
        # 37-byte message `seed(32) | round(1) | chunk_le(4)` packed into
        # one padded SHA-256 block: word8 = round | chunk bytes 0-2,
        # word9 = chunk byte 3 | 0x80 terminator, word15 = bit length.
        chunk = (position >> 8).astype(jnp.uint32)
        r32 = r.astype(jnp.uint32)
        w8 = (
            (r32 << 24)
            | ((chunk & 0xFF) << 16)
            | (((chunk >> 8) & 0xFF) << 8)
            | ((chunk >> 16) & 0xFF)
        )
        w9 = (((chunk >> 24) & 0xFF) << 24) | jnp.uint32(0x80 << 16)
        msg = jnp.concatenate(
            [
                seed_b,
                jnp.stack(
                    [w8, w9, zero_w, zero_w, zero_w, zero_w, zero_w, len_w],
                    axis=2,
                ),
            ],
            axis=2,
        )
        dig = _compress(h0, msg.reshape(s * k, 16)).reshape(s, k, 8)
        byte_idx = (jnp.mod(position, 256) >> 3).astype(jnp.int32)
        word = jnp.take_along_axis(
            dig, (byte_idx >> 2)[..., None], axis=2)[..., 0]
        shift = (((3 - (byte_idx & 3)) * 8)).astype(jnp.uint32)
        byte = (word >> shift) & jnp.uint32(0xFF)
        bit = (byte.astype(jnp.int32) >> (position & 7)) & 1
        return jnp.where(bit == 1, flip, idx)

    idx = jax.lax.fori_loop(0, r_count, round_body, idx)
    eff_c = eff_act[idx]
    accept = eff_c * jnp.int64(255) >= max_eb * rbytes.astype(jnp.int64)
    found = accept.any(axis=1)
    first = jnp.argmax(accept, axis=1)
    pos = jnp.take_along_axis(idx, first[:, None], axis=1)[:, 0]
    return jnp.where(found, pos, -1), found


@jax.jit
def _proposer_kernel(seed_words, pivots, rbytes, eff_act, m_live, max_eb):
    return _proposer_core(seed_words, pivots, rbytes, eff_act, m_live,
                          max_eb)


@partial(jax.jit, static_argnames=("in_leak",))
def _boundary_kernel(
    eff_bal,            # (nb,) int64
    activation_epoch,   # (nb,) int64
    exit_epoch,         # (nb,) int64
    withdrawable_epoch, # (nb,) int64
    slashed,            # (nb,) bool
    prev_part,          # (nb,) int64
    inactivity,         # (nb,) int64
    balance,            # (nb,) int64 pre-boundary balances
    act_elig_epoch,     # (nb,) int64
    eb_cap,             # (nb,) int64 per-validator hysteresis cap
    active_idx,         # (nb,) int32 active-at-next-epoch validator indices
    sh_pivots,          # (R,) int32 attester-shuffle pivots (list order)
    sh_digests,         # (R, chunks*32) uint8
    seed_words,         # (S, 8) uint32 per-slot proposer seeds
    prop_pivots,        # (S, R) int32 proposer pivots (forward order)
    rbytes,             # (S, K) int32
    previous_epoch, base_reward_per_increment, total_active_balance,
    increment, inactivity_score_bias, inactivity_score_recovery_rate,
    quotient, current_epoch, downward, upward, ejection_balance,
    far_future, finalized_epoch, max_eb, queue_lo, queue_hi,
    m_live,             # () int32 live active count
    *,
    in_leak: bool,
):
    """The fused epoch boundary: deltas + balance application + hysteresis
    and registry masks + next-epoch shuffling + per-slot proposer walk,
    one program."""
    new_inactivity, balance_delta = _deltas_core(
        eff_bal, activation_epoch, exit_epoch, withdrawable_epoch, slashed,
        prev_part, inactivity, previous_epoch, base_reward_per_increment,
        total_active_balance, increment, inactivity_score_bias,
        inactivity_score_recovery_rate, quotient, in_leak=in_leak,
    )
    new_bal = jnp.maximum(0, balance + balance_delta)
    new_eff, ejection_mask, queue_mask, activation_mask = _balance_core(
        new_bal, eff_bal, activation_epoch, exit_epoch, act_elig_epoch,
        eb_cap, current_epoch, increment, downward, upward,
        ejection_balance, far_future, finalized_epoch, queue_lo, queue_hi,
    )
    shuffled = _shuffle_core(active_idx, sh_pivots, sh_digests, m_live)
    # Proposer acceptance reads the POST-update effective balances — the
    # duty is looked up in the new epoch, after the transition applied.
    eff_act = new_eff[active_idx]
    pos, found = _proposer_core(
        seed_words, prop_pivots, rbytes, eff_act, m_live, max_eb)
    proposer = jnp.where(
        found, active_idx[jnp.maximum(pos, 0)].astype(jnp.int64), -1)
    return (new_inactivity, balance_delta, new_eff, ejection_mask,
            queue_mask, activation_mask, shuffled, proposer, found)


# --------------------------------------------- vocabulary + bucket + AOT


def _aot_warmup_shuffle(nb: int) -> None:
    from .compile_cache import aot_warmup_op

    aot_warmup_op("shuffle", nb)


def _aot_warmup_proposer(nb: int) -> None:
    from .compile_cache import aot_warmup_op

    aot_warmup_op("proposer_select", nb)


def _aot_warmup_boundary(nb: int) -> None:
    from .compile_cache import aot_warmup_op

    aot_warmup_op("epoch_boundary", nb)


autotune.register_vocabulary(
    "shuffle", N_BUCKETS,
    telemetry_ops=("shuffle",),
    budget_key=lambda nb: f"shuffle|-|{nb}|-",
    warmup=_aot_warmup_shuffle,
)

autotune.register_vocabulary(
    "proposer_select", N_BUCKETS,
    telemetry_ops=("proposer_select",),
    budget_key=lambda nb: f"proposer_select|-|{nb}|-",
    warmup=_aot_warmup_proposer,
)

# Like epoch_deltas, the boundary forks its compiled program on in_leak, so
# one adopted bucket must be budgeted and warmed for BOTH lowerings.
autotune.register_vocabulary(
    "epoch_boundary", N_BUCKETS,
    telemetry_ops=("epoch_boundary", "epoch_boundary_leak"),
    budget_key=lambda nb: (f"epoch_boundary|-|{nb}|-",
                           f"epoch_boundary_leak|-|{nb}|-"),
    warmup=_aot_warmup_boundary,
)


def _bucket(op: str, n: int) -> int:
    """The lane bucket for ``n`` rows of ``op`` (exact size past the top),
    against the live vocabulary (static :data:`N_BUCKETS` + any
    controller-adopted overlay buckets)."""
    for b in autotune.bucket_vocabulary(op, N_BUCKETS):
        if n <= b:
            return b
    return n


#: The three kernels behind the dispatch seam.  Only the fused boundary
#: shards over a mesh; the shuffle and the proposer walk always dispatch on
#: one device.
SHUFFLE = dispatch.Program(_shuffle_kernel)
PROPOSER = dispatch.Program(_proposer_kernel, x64=True,  # bucket: eff_act
                            shape=lambda args: (int(args[3].shape[0]),))
BOUNDARY = dispatch.Program(_boundary_kernel, fills=_PAD_FILLS, x64=True)


# ------------------------------------------------- host-side table builds


def shuffle_tables(seed: bytes, rounds: int, n: int,
                   nb: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pivot/digest tables for a whole-LIST shuffle over ``n`` live lanes
    padded to ``nb``: rows are host-reversed into list application order
    (decreasing round first) so the kernel walks forward; chunks past the
    live range are zero (only pad lanes can index them, and their swap is
    masked off)."""
    from ..consensus.shuffling import round_digest_table

    chunks = _chunk_count(nb)
    pivots = np.zeros(rounds, dtype=np.int32)
    digests = np.zeros((rounds, chunks * 32), dtype=np.uint8)
    if n > 1 and rounds > 0:
        live_chunks = (n + 255) // 256
        p, d = round_digest_table(seed, rounds, live_chunks, n)
        pivots[:] = p[::-1].astype(np.int32)
        digests[:, : live_chunks * 32] = d[::-1]
    return pivots, digests


def proposer_tables(
    slot_seeds: Sequence[bytes], rounds: int, m: int,
    k: int = PROPOSER_CANDIDATES,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-slot seed words, forward-order pivots, and acceptance random
    bytes for the device candidate walk (``m`` live active validators)."""
    s = len(slot_seeds)
    seed_words = np.zeros((s, 8), dtype=np.uint32)
    pivots = np.zeros((s, rounds), dtype=np.int32)
    rbytes = np.zeros((s, k), dtype=np.int32)
    m_mod = max(m, 1)
    for si, seed in enumerate(slot_seeds):
        seed_words[si] = np.frombuffer(seed, dtype=">u4")
        for r in range(rounds):
            pivots[si, r] = int.from_bytes(
                sha256(seed + bytes([r])).digest()[:8], "little") % m_mod
        for g in range((k + 31) // 32):
            block = np.frombuffer(
                sha256(seed + g.to_bytes(8, "little")).digest(),
                dtype=np.uint8,
            )
            take = min(32, k - g * 32)
            rbytes[si, g * 32:g * 32 + take] = block[:take]
    return seed_words, pivots, rbytes


# ------------------------------------------------------------ dispatches


def shuffle_device(values, seed: bytes, rounds: int) -> np.ndarray:
    """Device ``shuffle_list``: numpy in, numpy out, bit-identical to the
    host path (``out[i] = values[compute_shuffled_index(i)]``)."""
    arr = np.asarray(values)
    n = int(arr.shape[0])
    if n <= 1 or rounds == 0:
        return arr.copy()
    nb = _bucket("shuffle", n)
    pivots, digests = shuffle_tables(seed, rounds, n, nb)
    padded = np.zeros(nb, dtype=np.int32)
    padded[:n] = arr.astype(np.int32)
    shuffled = SHUFFLE.call((padded, pivots, digests, np.int32(n)), n_live=n)
    return np.asarray(shuffled[:n], dtype=arr.dtype)


def proposer_select_device(
    slot_seeds: Sequence[bytes],
    active_indices,
    effective_balance,
    *,
    rounds: int,
    max_effective_balance: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Device proposer selection for a batch of slot seeds over one active
    set.  ``effective_balance`` is indexed by VALIDATOR index (the registry
    array).  Returns ``(proposer, found)`` — ``proposer[s]`` is the spec's
    ``compute_proposer_index`` result whenever ``found[s]``; a not-found
    slot (all :data:`PROPOSER_CANDIDATES` rejected) stays on the scalar
    walk."""
    active = np.asarray(active_indices, dtype=np.int64)
    m = int(active.shape[0])
    s = len(slot_seeds)
    if m == 0 or s == 0:
        return (np.full(s, -1, dtype=np.int64), np.zeros(s, dtype=bool))
    nb = _bucket("proposer_select", m)
    seed_words, pivots, rbytes = proposer_tables(slot_seeds, rounds, m)
    eff = np.asarray(effective_balance, dtype=np.int64)
    eff_act = np.zeros(nb, dtype=np.int64)
    eff_act[:m] = eff[active]
    pos, found = PROPOSER.call(
        (seed_words, pivots, rbytes, eff_act, np.int32(m),
         np.int64(int(max_effective_balance))), n_live=m)
    pos = np.asarray(pos, dtype=np.int64)
    found = np.asarray(found, dtype=bool)
    proposer = np.where(found, active[np.maximum(pos, 0)], -1)
    return proposer, found


@dataclass
class BoundaryPlan:
    """Host-precomputed inputs for one fused epoch-boundary dispatch —
    built by ``per_epoch._build_boundary_plan`` from the state, consumed by
    both :func:`epoch_boundary_device` and the numpy fallback golden."""

    # registry arrays, each (n,)
    effective_balance: np.ndarray
    activation_epoch: np.ndarray
    exit_epoch: np.ndarray
    withdrawable_epoch: np.ndarray
    slashed: np.ndarray
    prev_part: np.ndarray
    inactivity: np.ndarray
    balance: np.ndarray
    activation_eligibility_epoch: np.ndarray
    eb_cap: np.ndarray
    # active validator indices at the NEXT epoch, (m,)
    active_idx: np.ndarray
    # seeds for the next epoch's duties
    attester_seed: bytes
    slot_seeds: Tuple[bytes, ...]
    rounds: int
    # scalars
    previous_epoch: int
    base_reward_per_increment: int
    total_active_balance: int
    increment: int
    inactivity_score_bias: int
    inactivity_score_recovery_rate: int
    quotient: int
    current_epoch: int
    downward: int
    upward: int
    ejection_balance: int
    far_future: int
    finalized_epoch: int
    max_effective_balance: int
    queue_lo: int
    queue_hi: int

    @property
    def n(self) -> int:
        return int(self.effective_balance.shape[0])

    @property
    def m(self) -> int:
        return int(self.active_idx.shape[0])


def synthetic_plan(n: int, seed: int) -> BoundaryPlan:
    """A mainnet-shaped synthetic BoundaryPlan over ``n`` validators: every
    registry field the fused kernel reads, with realistic distributions (a
    few exited / slashed / pending validators, gwei-scale balances), made
    from ``seed`` in bulk.  Used by ``bench.py --epoch-child`` and
    ``chip_smoke.py``."""
    import math

    rng = np.random.default_rng(seed)
    gwei = 10**9
    max_eb = 32 * gwei
    far_future = 2**63 - 1
    current_epoch = 5
    eff = (rng.integers(17, 33, size=n).astype(np.int64)) * gwei
    balance = eff + rng.integers(-2 * gwei, 2 * gwei, size=n)
    activation_epoch = np.zeros(n, dtype=np.int64)
    exit_epoch = np.full(n, far_future, dtype=np.int64)
    withdrawable_epoch = np.full(n, far_future, dtype=np.int64)
    act_elig = np.zeros(n, dtype=np.int64)
    # ~1% exited, ~0.5% slashed, ~0.5% still pending activation
    exited = rng.random(n) < 0.01
    exit_epoch[exited] = current_epoch - 1
    withdrawable_epoch[exited] = current_epoch + 200
    pending = (~exited) & (rng.random(n) < 0.005)
    activation_epoch[pending] = far_future
    act_elig[pending] = far_future
    slashed = (~exited) & (~pending) & (rng.random(n) < 0.005)
    active = (activation_epoch <= current_epoch + 1) & (
        current_epoch + 1 < exit_epoch)
    active_idx = np.nonzero(active)[0].astype(np.int64)
    total_active = int(eff[active].sum())
    increment = gwei
    hyst = increment // 4
    attester_seed = sha256(b"epoch-bench-att-%d" % seed).digest()
    slot_seeds = tuple(  # mainnet slots_per_epoch: one proposer per slot
        sha256(b"epoch-bench-slot-%d-%d" % (seed, s)).digest()
        for s in range(32))
    return BoundaryPlan(
        effective_balance=eff,
        activation_epoch=activation_epoch,
        exit_epoch=exit_epoch,
        withdrawable_epoch=withdrawable_epoch,
        slashed=slashed,
        prev_part=rng.integers(0, 8, size=n).astype(np.int64),
        inactivity=rng.integers(0, 12, size=n).astype(np.int64),
        balance=balance,
        activation_eligibility_epoch=act_elig,
        eb_cap=np.full(n, max_eb, dtype=np.int64),
        active_idx=active_idx,
        attester_seed=attester_seed,
        slot_seeds=slot_seeds,
        rounds=90,  # mainnet shuffle_round_count
        previous_epoch=current_epoch - 1,
        base_reward_per_increment=(
            increment * 64 // math.isqrt(max(total_active, 1))),
        total_active_balance=max(total_active, increment),
        increment=increment,
        inactivity_score_bias=4,
        inactivity_score_recovery_rate=16,
        quotient=2**24,
        current_epoch=current_epoch,
        downward=hyst,
        upward=hyst * 5,
        ejection_balance=16 * gwei,
        far_future=far_future,
        finalized_epoch=current_epoch - 2,
        max_effective_balance=max_eb,
        queue_lo=max_eb,
        queue_hi=max_eb,
    )


def epoch_boundary_device(plan: BoundaryPlan, *, in_leak: bool):
    """numpy in, numpy out — ONE supervised device program for the whole
    epoch boundary.  Returns ``(new_inactivity, balance_delta, new_eff,
    ejection_mask, queue_mask, activation_mask, shuffling, proposer,
    found)``; per-validator arrays sliced to ``plan.n``, the shuffling to
    ``plan.m``, proposer/found per slot."""
    n, m = plan.n, plan.m
    np_ = BOUNDARY.rows(_bucket("epoch_boundary", n))
    sh_pivots, sh_digests = shuffle_tables(
        plan.attester_seed, plan.rounds, m, np_)
    seed_words, prop_pivots, rbytes = proposer_tables(
        plan.slot_seeds, plan.rounds, m)
    active_padded = np.zeros(np_, dtype=np.int32)
    active_padded[:m] = plan.active_idx.astype(np.int32)
    batched = (
        np.asarray(plan.effective_balance, dtype=np.int64),
        np.asarray(plan.activation_epoch, dtype=np.int64),
        np.asarray(plan.exit_epoch, dtype=np.int64),
        np.asarray(plan.withdrawable_epoch, dtype=np.int64),
        np.asarray(plan.slashed, dtype=bool),
        np.asarray(plan.prev_part, dtype=np.int64),
        np.asarray(plan.inactivity, dtype=np.int64),
        np.asarray(plan.balance, dtype=np.int64),
        np.asarray(plan.activation_eligibility_epoch, dtype=np.int64),
        np.asarray(plan.eb_cap, dtype=np.int64),
        active_padded,
    )
    tables = (sh_pivots, sh_digests, seed_words, prop_pivots, rbytes)
    scalars = tuple(np.int64(v) for v in (
        plan.previous_epoch, plan.base_reward_per_increment,
        plan.total_active_balance, plan.increment,
        plan.inactivity_score_bias,
        plan.inactivity_score_recovery_rate, plan.quotient,
        plan.current_epoch, plan.downward, plan.upward,
        plan.ejection_balance, plan.far_future, plan.finalized_epoch,
        plan.max_effective_balance, plan.queue_lo, plan.queue_hi,
    ))
    (new_inactivity, balance_delta, new_eff, ejection_mask, queue_mask,
     activation_mask, shuffled, proposer, found) = BOUNDARY.call(
        batched + tables + scalars + (np.int32(m),), n_live=n, rows=np_,
        op="epoch_boundary_leak" if in_leak else "epoch_boundary",
        static={"in_leak": bool(in_leak)})
    return (
        np.asarray(new_inactivity[:n], dtype=np.int64),
        np.asarray(balance_delta[:n], dtype=np.int64),
        np.asarray(new_eff[:n], dtype=np.int64),
        np.asarray(ejection_mask[:n], dtype=bool),
        np.asarray(queue_mask[:n], dtype=bool),
        np.asarray(activation_mask[:n], dtype=bool),
        np.asarray(shuffled[:m], dtype=np.int64),
        np.asarray(proposer, dtype=np.int64),
        np.asarray(found, dtype=bool),
    )
