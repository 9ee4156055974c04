"""Batched blob-KZG-proof verification on the device pairing kernel.

The Deneb data-availability hot path (reference
``beacon_node/beacon_chain/src/kzg_utils.rs:23-36`` →
``c_kzg::KzgProof::verify_blob_kzg_proof_batch``) reformulated TPU-first:
the random-linear-combination MSMs (three N-point G1 MSMs + one generator
multiplication) AND the final 2-pairing all run inside one fused device
program, batched over the blob axis — the BASELINE.md Deneb target shape is
6 blobs x 32 blocks = 192 lanes through these MSMs.

Host responsibilities (trusted side, mirroring ops/verify.py): Fiat-Shamir
challenges, polynomial evaluation over the blob field elements, byte
parsing/subgroup checks, and the exact ``fe == 1`` verdict.

Verification equation (crypto/kzg/kzg.py _verify_kzg_proof_batch, the host
golden model this program must agree with exactly):

    e(sum_i [r_i] P_i, [tau]G2) * e(-(sum_i [r_i](C_i - [y_i]G1 + [z_i]P_i)), G2) == 1
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import ec, pairing, tower

N_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)


@jax.jit
def _device_kzg_batch(c, p, r_bits, rz_bits, ry_bits, tau, g2gen):
    """c, p: G1 projective coords (N, 25) x3 (commitments, proofs);
    r_bits, rz_bits: (N, 256) int32 MSB-first scalars (r_i, r_i*z_i mod R);
    ry_bits: (256,) — sum_i r_i*y_i mod R; tau, g2gen: affine twist (2, 25) x2.
    Returns the final-exponentiation output limbs (host-checks == 1).

    Named scopes (HLO metadata only) mark the stages that
    ``device_telemetry.program_stages`` maps the compiled instructions to:
    the four bit ladders (``k1_g1_ladders``), the tree sums and adds
    (``k2_lincomb``), then the pairing's ``miller_loop`` and ``final_exp``.
    """
    with jax.named_scope("k1_g1_ladders"):
        c_w = ec.scalar_mul_bits(ec.G1_OPS, c, r_bits)       # [r_i] C_i
        p_w = ec.scalar_mul_bits(ec.G1_OPS, p, r_bits)       # [r_i] P_i
        pz_w = ec.scalar_mul_bits(ec.G1_OPS, p, rz_bits)     # [r_i z_i] P_i

    with jax.named_scope("k2_lincomb"):
        proof_lincomb = ec.tree_sum(ec.G1_OPS, p_w, axis=0)
        c_lincomb = ec.tree_sum(ec.G1_OPS, c_w, axis=0)
        pz_lincomb = ec.tree_sum(ec.G1_OPS, pz_w, axis=0)

    with jax.named_scope("k1_g1_ladders"):
        gen = tuple(jnp.asarray(x) for x in ec.G1_GEN_LIMBS)
        gen_ry = ec.scalar_mul_bits(ec.G1_OPS, gen, ry_bits)  # [sum r_i y_i] G1

    with jax.named_scope("k2_lincomb"):
        rhs = ec.point_add(
            ec.G1_OPS,
            ec.point_add(ec.G1_OPS, c_lincomb, ec.point_neg(gen_ry)),
            pz_lincomb,
        )
        p1 = tuple(jnp.stack([a, b])
                   for a, b in zip(proof_lincomb, ec.point_neg(rhs)))
    q2 = tuple(jnp.stack([a, b]) for a, b in zip(tau, g2gen))
    mask = jnp.asarray([True, True])
    return pairing.multi_pairing_fe(p1, q2, mask)


#: The named scopes of ``_device_kzg_batch``'s stages, in program order.
STAGES = ("k1_g1_ladders", "k2_lincomb", "miller_loop", "final_exp")


def lower_for_stages(shape):
    """``_device_kzg_batch`` lowered at the bucket ``(nb,)`` with the
    dispatched arguments' types, so jit's caches hand back the executable
    the dispatches ran (``device_telemetry.program_stages``)."""
    (nb,) = shape

    def i32(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32)

    g1 = tuple(i32(nb, 25) for _ in range(3))
    twist = tuple(i32(2, 25) for _ in range(2))
    return _device_kzg_batch.lower(g1, g1, i32(nb, 256), i32(nb, 256),
                                   i32(256), twist, twist)


def _bucket(n: int) -> int:
    for b in N_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"kzg batch of {n} exceeds max bucket {N_BUCKETS[-1]}")


#: device_mesh.ShardedEntry for the kzg program (lazy).  The blob-axis
#: tree-sum lincombs reduce across the mesh through XLA-inserted psums —
#: the ``reduces_over_batch`` op the registry note promised, and why it
#: sits in ``device_supervisor.NO_SPLIT_OPS``.
_SHARDED_ENTRY = None

ENTRY_KEY = "lighthouse_tpu/ops/kzg_device.py:_device_kzg_batch"


def _sharded_entry():
    global _SHARDED_ENTRY
    if _SHARDED_ENTRY is None:
        from .. import device_mesh

        _SHARDED_ENTRY = device_mesh.ShardedEntry(
            ENTRY_KEY, _device_kzg_batch.__wrapped__
        )
    return _SHARDED_ENTRY


def _build_kzg_batch(c_pts, p_pts, r_powers, zs, ys, g2_tau, nb: int):
    """Host-side marshalling (limb packing, scalar-bit expansion) into
    padded device arrays — no device work beyond the uploads."""
    from ..crypto.bls.params import R

    n = len(c_pts)
    id1 = ec.g1_to_limbs(None)
    c = [np.tile(np.asarray(x), (nb, 1)) for x in id1]
    p = [np.tile(np.asarray(x), (nb, 1)) for x in id1]
    r_bits = np.zeros((nb, 256), np.int32)
    rz_bits = np.zeros((nb, 256), np.int32)
    ry = 0
    for i in range(n):
        cl = ec.g1_to_limbs(c_pts[i])
        pl = ec.g1_to_limbs(p_pts[i])
        for coord in range(3):
            c[coord][i] = cl[coord]
            p[coord][i] = pl[coord]
        r_bits[i] = ec.bits_msb(r_powers[i] % R, 256)
        rz_bits[i] = ec.bits_msb(r_powers[i] * zs[i] % R, 256)
        ry = (ry + r_powers[i] * ys[i]) % R
    ry_bits = ec.bits_msb(ry, 256)

    tau = (tower.fq2_to_limbs(g2_tau[0]), tower.fq2_to_limbs(g2_tau[1]))
    g2gen = (
        np.asarray(ec.G2_GEN_LIMBS[0]),
        np.asarray(ec.G2_GEN_LIMBS[1]),
    )
    host = (
        tuple(c), tuple(p), r_bits, rz_bits,
        np.asarray(ry_bits),
        tuple(np.asarray(a) for a in tau),
        tuple(np.asarray(a) for a in g2gen),
    )
    from .. import device_mesh

    if device_mesh.enabled():
        # nb was already padded to a multiple of the mesh by the caller;
        # the identity-point + zero-scalar pad rows contribute exact
        # neutral elements to the psum'd lincombs.
        return _sharded_entry().place(*host)
    return (
        tuple(jnp.asarray(a) for a in host[0]),
        tuple(jnp.asarray(a) for a in host[1]),
        jnp.asarray(host[2]),
        jnp.asarray(host[3]),
        jnp.asarray(host[4]),
        tuple(jnp.asarray(a) for a in host[5]),
        tuple(jnp.asarray(a) for a in host[6]),
    )


def verify_kzg_proof_batch_device(
    c_pts: Sequence, p_pts: Sequence, r_powers: Sequence[int],
    zs: Sequence[int], ys: Sequence[int], g2_tau,
    host_fn=None,
) -> bool:
    """Run the device program on parsed host points + scalars.

    ``c_pts``/``p_pts``: host affine G1 (Fq pairs or None for infinity);
    ``g2_tau``: host Fq2 affine point ([tau]G2 from the trusted setup).

    Supervised (device_supervisor.py) like the other bucketed entry points:
    the dispatch + the ``fe == 1`` materialization run on the watchdog
    worker — the blob-DA caller (block import) never blocks inside a device
    sync — and a hung or failing device resolves through ``host_fn`` (the
    host MSM golden model in ``crypto/kzg/kzg.py``) under the one shared
    breaker/fallback mechanism.  With ``host_fn=None`` failures propagate.
    """
    from .. import (device_mesh, device_supervisor, device_telemetry,
                    fault_injection, tracing)

    n = len(c_pts)
    assert n == len(p_pts) == len(r_powers) == len(zs) == len(ys)
    holder: dict = {}

    def device_fn() -> bool:
        # Runs on the supervisor's worker with the caller's span attached:
        # the kzg_batch_* spans land in the caller's trace, and their
        # durations are the flight record's stages.
        stages_local: dict = {}
        state_local: dict = {}
        try:
            # Marshalling (and its host→device uploads) happens INSIDE the
            # supervised leg: an OPEN breaker must not touch the device at
            # all, and a transfer raising on a dead device resolves through
            # the host fallback like any other device failure.  Bucket and
            # mesh pad are (re)computed here too, so a supervisor reshard
            # retry re-places under the surviving topology.
            with tracing.span("kzg_batch_setup", n_blobs=n) as sp_setup:
                mesh = device_mesh.size() if device_mesh.enabled() else 0
                nb = _bucket(max(1, n))
                if mesh:
                    nb = device_mesh.pad_rows(nb)
                state_local["mesh"], state_local["nb"] = mesh, nb
                batch = _build_kzg_batch(c_pts, p_pts, r_powers, zs, ys,
                                         g2_tau, nb)
            stages_local["setup"] = sp_setup.duration
            if fault_injection.ACTIVE:
                if not device_telemetry.COMPILE_CACHE.seen("kzg_batch", (nb,),
                                                           mesh=mesh):
                    fault_injection.check("device.compile", op="kzg_batch")
                fault_injection.check("device.dispatch", op="kzg_batch")
            with tracing.span("kzg_batch_dispatch", n_bucket=nb,
                              mesh=mesh) as sp_dispatch:
                if mesh:
                    fe = _sharded_entry()(*batch)
                else:
                    fe = _device_kzg_batch(*batch)
            stages_local["dispatch"] = sp_dispatch.duration
            if device_telemetry.note_dispatch("kzg_batch", (nb,),
                                              sp_dispatch.duration, mesh=mesh):
                sp_dispatch.fields["compiled"] = True
                state_local["compiled"] = True
            with tracing.span("kzg_batch_wait", n_bucket=nb) as sp_wait:
                jax.block_until_ready(fe)
            stages_local["wait"] = sp_wait.duration
            with tracing.span("kzg_batch_verdict") as sp_verdict:
                try:
                    return pairing.fe_is_one(fe)
                finally:
                    stages_local["verdict"] = sp_verdict.duration
        finally:
            holder["stages"] = stages_local
            holder["state"] = state_local

    info: dict = {}
    ok = device_supervisor.run(
        "kzg_batch",
        device_fn,
        host_fn=host_fn,
        info=info,
    )
    reason = info.get("fallback_reason")
    stages: dict = {}
    compiled = False
    state: dict = {}
    if reason != "dispatch_timeout":
        stages = holder.get("stages") or {}
        state = holder.get("state") or {}
        compiled = state.get("compiled", False)
    mesh = state.get("mesh", 0)
    nb = state.get("nb", _bucket(max(1, n)))
    device_telemetry.record_batch(
        op="kzg_batch",
        shape=(nb,),
        n_live=n,
        stages=stages or None,
        verdict=bool(ok),
        host_fallback=info.get("route") == "host",
        fallback_reason=reason,
        trace_id=device_telemetry.active_trace_id(),
        compiled=compiled,
        breaker_state=info.get("breaker_state"),
        dispatched=reason != "breaker_open",
        mesh=mesh,
        shard_live=(_sharded_entry().shard_live_counts(n, nb)
                    if mesh else None),
    )
    return bool(ok)
