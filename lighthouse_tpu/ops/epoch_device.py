"""Device (jnp) epoch-processing deltas: the fused per-validator pass.

The altair+ epoch transition's per-validator math — inactivity-score
updates, participation-flag rewards, penalties, inactivity penalties —
expressed as one fused elementwise jnp program over the ``EpochArrays``
contract (consensus/per_epoch.py).  This is the TPU analog of the
reference's ``single_pass.rs`` fused epoch loop: at 1M validators the pass
is pure memory-bound vector arithmetic, exactly what XLA fuses into a
handful of kernels.

Epoch math needs 64-bit integers (effective balances are ~3.2e10 gwei and
reward intermediates reach ~1e13), so dispatch runs under the
``jax.enable_x64`` context — scoped to these calls, leaving
the int32-limb BLS kernels untouched.

Shape discipline: registries dispatch at power-of-two **registry buckets**
(:data:`N_BUCKETS`, through 2^20 validators — mainnet shape), padded with
never-active rows (far-future activation epoch, zero balance) that are
ineligible for every flag mask and therefore contribute exactly zero to the
registry-wide participating-increment sums.  A ~1M-validator network
compiles a handful of executables instead of one per registry size — the
same bucket story as ``ops/verify.py``/``ops/sha256_device.py``, and what
lets the registry grow every epoch without a recompile.

Semantics are bit-identical to the numpy path (same floor divisions, same
masks); tests assert equality on randomized registries, including
non-power-of-two live counts against exact-size golden runs
(tests/test_epoch_buckets.py).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .. import autotune
from . import dispatch
from ..types.spec import (
    PARTICIPATION_FLAG_WEIGHTS,
    TIMELY_HEAD_FLAG_INDEX,
    TIMELY_TARGET_FLAG_INDEX,
    WEIGHT_DENOMINATOR,
)


def _deltas_core(
    eff_bal,            # (n,) int64 gwei
    activation_epoch,   # (n,) int64
    exit_epoch,         # (n,) int64
    withdrawable_epoch, # (n,) int64
    slashed,            # (n,) bool
    prev_part,          # (n,) int64 flag bits
    inactivity,         # (n,) int64
    previous_epoch,     # () int64
    base_reward_per_increment,  # () int64
    total_active_balance,       # () int64
    increment,          # () int64
    inactivity_score_bias,      # () int64
    inactivity_score_recovery_rate,  # () int64
    quotient,           # () int64
    *,
    in_leak: bool,
):
    """Traceable body of the deltas pass — shared between the standalone
    :func:`_deltas_kernel` entry and the fused epoch-boundary program
    (``ops/shuffle_device.py:_boundary_kernel``)."""
    active_prev = (activation_epoch <= previous_epoch) & (previous_epoch < exit_epoch)
    eligible = active_prev | (slashed & (previous_epoch + 1 < withdrawable_epoch))

    def flag_mask(flag_index):
        return (
            ((prev_part >> flag_index) & 1).astype(bool)
            & active_prev
            & ~slashed
        )

    prev_target = flag_mask(TIMELY_TARGET_FLAG_INDEX)

    # --- inactivity updates (spec process_inactivity_updates)
    delta = jnp.where(
        prev_target, -jnp.minimum(1, inactivity), inactivity_score_bias
    )
    new_inactivity = inactivity + jnp.where(eligible, delta, 0)
    if not in_leak:
        new_inactivity = new_inactivity - jnp.where(
            eligible,
            jnp.minimum(inactivity_score_recovery_rate, new_inactivity),
            0,
        )

    # --- rewards and penalties (spec process_rewards_and_penalties)
    base_reward = (eff_bal // increment) * base_reward_per_increment
    active_increments = total_active_balance // increment
    rewards = jnp.zeros_like(eff_bal)
    penalties = jnp.zeros_like(eff_bal)
    for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
        participating = flag_mask(flag_index)
        participating_increments = (
            jnp.where(participating, eff_bal, 0).sum() // increment
        )
        if not in_leak:
            flag_rewards = (
                base_reward * weight * participating_increments
                // (active_increments * WEIGHT_DENOMINATOR)
            )
            rewards = rewards + jnp.where(
                eligible & participating, flag_rewards, 0
            )
        if flag_index != TIMELY_HEAD_FLAG_INDEX:
            penalties = penalties + jnp.where(
                eligible & ~participating,
                base_reward * weight // WEIGHT_DENOMINATOR,
                0,
            )
    inactivity_penalty = (
        eff_bal * new_inactivity // (inactivity_score_bias * quotient)
    )
    penalties = penalties + jnp.where(
        eligible & ~prev_target, inactivity_penalty, 0
    )
    return new_inactivity, rewards - penalties


@partial(jax.jit, static_argnames=("in_leak",))
def _deltas_kernel(
    eff_bal, activation_epoch, exit_epoch, withdrawable_epoch, slashed,
    prev_part, inactivity, previous_epoch, base_reward_per_increment,
    total_active_balance, increment, inactivity_score_bias,
    inactivity_score_recovery_rate, quotient, *, in_leak: bool,
):
    return _deltas_core(
        eff_bal, activation_epoch, exit_epoch, withdrawable_epoch, slashed,
        prev_part, inactivity, previous_epoch, base_reward_per_increment,
        total_active_balance, increment, inactivity_score_bias,
        inactivity_score_recovery_rate, quotient, in_leak=in_leak,
    )


def _balance_core(
    balance,            # (n,) int64 post-delta balances
    eff_bal,            # (n,) int64 current effective balances
    activation_epoch,   # (n,) int64
    exit_epoch,         # (n,) int64
    act_elig_epoch,     # (n,) int64 activation_eligibility_epoch
    eb_cap,             # (n,) int64 per-validator effective-balance cap
    current_epoch,      # () int64
    increment,          # () int64
    downward,           # () int64 hysteresis downward threshold
    upward,             # () int64 hysteresis upward threshold
    ejection_balance,   # () int64
    far_future,         # () int64 FAR_FUTURE_EPOCH (clamped to int64)
    finalized_epoch,    # () int64
    queue_lo,           # () int64 activation-queue eligibility low bound
    queue_hi,           # () int64 activation-queue eligibility high bound
):
    """Effective-balance hysteresis + registry-update masks, the device
    half of ``per_epoch._process_effective_balance_updates`` /
    ``_process_registry_updates``.  Bucket-pad rows (zero balances,
    activation epoch ``_PAD_ACTIVATION_EPOCH``, eligibility epoch 0,
    cap 1) satisfy none of the masks and keep a zero effective balance.

    Returns ``(new_eff, ejection_mask, queue_mask, activation_mask)``.
    """
    needs = (balance + downward < eff_bal) | (eff_bal + upward < balance)
    new_eff = jnp.where(
        needs,
        jnp.minimum(balance - jnp.mod(balance, increment), eb_cap),
        eff_bal,
    )
    active_cur = (activation_epoch <= current_epoch) & (
        current_epoch < exit_epoch)
    ejection_mask = active_cur & (eff_bal <= ejection_balance)
    queue_mask = (
        (act_elig_epoch == far_future)
        & (eff_bal >= queue_lo)
        & (eff_bal <= queue_hi)
    )
    activation_mask = (act_elig_epoch <= finalized_epoch) & (
        activation_epoch == far_future)
    return new_eff, ejection_mask, queue_mask, activation_mask


ENTRY_KEY = "lighthouse_tpu/ops/epoch_device.py:_deltas_kernel"

#: Epoch far beyond any reachable epoch: bucket- and mesh-pad rows use it
#: as their activation epoch so they are never active/eligible and
#: contribute exactly zero to every registry-wide sum.
_PAD_ACTIVATION_EPOCH = 1 << 62

#: Power-of-two registry buckets through 2^20 validators.  The bottom
#: bucket keeps the tier-1/minimal-preset registries on one tiny
#: executable; the top covers mainnet's ~1M.  A registry past the top
#: bucket dispatches at its exact size — that is decades of deposits away,
#: and one oversized executable beats refusing to process the chain.
N_BUCKETS = (64, 256, 1024, 4096, 16384, 65536, 262144, 1048576)

#: Per-pad-row fill values for the batched argument tuple (eff_bal,
#: activation, exit, withdrawable, slashed, prev_part, inactivity): a row
#: that is never active, never eligible, carries no balance and no flags.
_PAD_FILLS = (0, _PAD_ACTIVATION_EPOCH, 0, 0, False, 0, 0)


def _aot_warmup(nb: int) -> None:
    from .compile_cache import aot_warmup_op

    aot_warmup_op("epoch_deltas", nb)


# Self-tuning enrolment (autotune.py): the registry vocabulary is ratio-4
# past the 256 bucket, so a mid-size network parked between buckets (say
# ~2.5k validators padding to 4096) can earn a midpoint registry bucket.
# One adoption must be budgeted for BOTH lowerings (leak and non-leak —
# in_leak forks the compiled program like a shape does), and the warmup
# pays both compiles off-path.
autotune.register_vocabulary(
    "epoch_deltas", N_BUCKETS,
    telemetry_ops=("epoch_deltas", "epoch_deltas_leak"),
    budget_key=lambda nb: (f"epoch_deltas|-|{nb}|-",
                           f"epoch_deltas_leak|-|{nb}|-"),
    warmup=_aot_warmup,
)


def _bucket(n: int) -> int:
    """The registry bucket for ``n`` validators (exact size past the top),
    against the live vocabulary (static :data:`N_BUCKETS` + any
    controller-adopted overlay buckets)."""
    for b in autotune.bucket_vocabulary("epoch_deltas", N_BUCKETS):
        if n <= b:
            return b
    return n


#: The deltas kernel behind the dispatch seam.  Its registry-wide
#: participating-increment sums lower through XLA-inserted psums on the
#: mesh — which is exactly why the op sits in
#: ``device_supervisor.NO_SPLIT_OPS``.
PROGRAM = dispatch.Program(_deltas_kernel, fills=_PAD_FILLS, x64=True)


def epoch_deltas_device(
    arrays,
    prev_part: np.ndarray,
    inactivity: np.ndarray,
    *,
    previous_epoch: int,
    in_leak: bool,
    base_reward_per_increment: int,
    total_active_balance: int,
    quotient: int,
    spec,
):
    """numpy in, numpy out — the device analog of the per_epoch numpy block.
    Returns ``(new_inactivity, balance_delta)`` (int64 arrays).

    The registry pads to its power-of-two bucket (:data:`N_BUCKETS`) —
    mesh on, additionally to a multiple of the mesh size — with never-active
    rows (far-future activation: ineligible for every flag mask, so the
    participating-increment sums/psums are untouched); the pad rows are
    sliced back off the outputs."""
    # One executable per (registry-bucket, in_leak) pair — in_leak is a
    # static argument, so it forks the compiled program like a shape does.
    n = int(np.asarray(arrays.effective_balance).shape[0])
    host = (
        np.asarray(arrays.effective_balance, dtype=np.int64),
        np.asarray(arrays.activation_epoch, dtype=np.int64),
        np.asarray(arrays.exit_epoch, dtype=np.int64),
        np.asarray(arrays.withdrawable_epoch, dtype=np.int64),
        np.asarray(arrays.slashed, dtype=bool),
        np.asarray(prev_part, dtype=np.int64),
        np.asarray(inactivity, dtype=np.int64),
    ) + tuple(np.int64(s) for s in (
        previous_epoch, base_reward_per_increment, total_active_balance,
        spec.effective_balance_increment, spec.inactivity_score_bias,
        spec.inactivity_score_recovery_rate, quotient,
    ))
    new_inactivity, balance_delta = PROGRAM.call(
        host, n_live=n, rows=_bucket(n),
        op="epoch_deltas_leak" if in_leak else "epoch_deltas",
        static={"in_leak": bool(in_leak)})
    return (
        np.asarray(new_inactivity[:n], dtype=np.int64),
        np.asarray(balance_delta[:n], dtype=np.int64),
    )
