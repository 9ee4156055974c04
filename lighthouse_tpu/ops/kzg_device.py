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

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .. import tracing
from . import dispatch, ec, pairing, tower

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


#: ``_device_kzg_batch`` behind the dispatch seam.  On a mesh the blob-axis
#: tree-sum lincombs reduce across devices through XLA-inserted psums — why
#: the op sits in ``device_supervisor.NO_SPLIT_OPS``; its pad rows
#: (identity points, zero scalars) add exact neutral elements to them.
_ID1 = ec.g1_to_limbs(None)
PROGRAM = dispatch.Program(_device_kzg_batch, fills=(_ID1, _ID1, 0, 0),
                           spans="kzg_batch")


def _build_kzg_batch(c_pts, p_pts, r_powers, zs, ys, g2_tau, nb: int):
    """Host-side marshalling (limb packing, scalar-bit expansion) into
    padded host arrays: the arguments of ``_device_kzg_batch``."""
    from ..crypto.bls.params import R

    n = len(c_pts)
    c = [np.tile(np.asarray(x), (nb, 1)) for x in _ID1]
    p = [np.tile(np.asarray(x), (nb, 1)) for x in _ID1]
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
    return (
        tuple(c), tuple(p), r_bits, rz_bits,
        np.asarray(ry_bits),
        tuple(np.asarray(a) for a in tau),
        tuple(np.asarray(a) for a in g2gen),
    )


def _verdict(fe) -> bool:
    # ``pairing.fe_is_one`` is looked up per call, so a fault planted there
    # (``benchmark/faults.py``) reaches the device leg.
    return pairing.fe_is_one(fe)


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
    n = len(c_pts)
    assert n == len(p_pts) == len(r_powers) == len(zs) == len(ys)
    nb = _bucket(max(1, n))

    def setup() -> dispatch.Placed:
        # Marshalling and its uploads run on the supervised leg: an OPEN
        # breaker must not touch the device at all, a transfer raising on a
        # dead device resolves through the host fallback like any other
        # device failure, and a reshard retry places under the surviving
        # topology.
        with tracing.span("kzg_batch_setup", n_blobs=n) as sp_setup:
            placed = dispatch.Placed(PROGRAM, _build_kzg_batch(
                c_pts, p_pts, r_powers, zs, ys, g2_tau, nb))
        placed.setup_s = sp_setup.duration
        return placed

    return bool(PROGRAM.run(setup, n_live=n, host_fn=host_fn,
                            verdict=_verdict, bucket=(nb,)))
