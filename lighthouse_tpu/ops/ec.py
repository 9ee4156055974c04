"""Branch-free elliptic-curve group ops for G1(Fq) and G2(Fq2) on TPU.

Uses the *complete* projective addition/doubling formulas for j-invariant-0
short-Weierstrass curves (Renes–Costello–Batina 2015, algorithms 7/9): a single
algebraic path covers generic addition, doubling, inputs at infinity and
P + (-P), with the identity represented as (0 : 1 : 0).  No data-dependent
control flow — exactly what SPMD batching over signature sets needs (the role
rayon-chunked blst point ops play in the reference's
``consensus/state_processing/src/per_block_processing/block_signature_verifier.rs``).

Points are pytrees ``(X, Y, Z)`` of limb arrays; G1 coords are (..., 25),
G2 coords (..., 2, 25).  All functions are generic over the two fields via a
small op-table, so the same code path serves both groups.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.bls import curve as _curve
from ..crypto.bls import fields as hf
from ..crypto.bls.params import (G1_X, G1_Y, G2_X_C0, G2_X_C1, G2_Y_C0, G2_Y_C1,
                                 P, X_ABS)
from . import fq as _fq
from . import tower as _tw


class FieldOps(NamedTuple):
    mul: callable
    square: callable
    mul_small: callable
    mul_by_b3: callable      # multiply by 3*b of the curve
    zero: jax.Array
    one: jax.Array
    # many(muls, squares) -> (mul_results, square_results): all the round's
    # independent products fused into ONE conv+reduce pipeline, so a group
    # law's 6-mul round is one wide contraction instead of 6 narrow ones.
    many: callable


def _g1_mul_by_b3(x):
    return _fq.fq_mul_small(x, 12)          # b = 4


def _g2_mul_by_b3(x):
    # b' = 4(1+u); 3b' = 12(1+u) = 12 * xi
    return _tw.fq2_mul_by_xi(_tw.fq2_mul_small(x, 12))


def _g1_many(muls=(), squares=()):
    # Fq squaring IS fq_mul(a, a) — fold the squares into the same pipeline.
    outs = _fq.fq_mul_many(list(muls) + [(s, s) for s in squares])
    return outs[: len(muls)], outs[len(muls):]


G1_OPS = FieldOps(_fq.fq_mul, _fq.fq_square, _fq.fq_mul_small, _g1_mul_by_b3,
                  _fq.FQ_ZERO, _fq.FQ_ONE, _g1_many)
G2_OPS = FieldOps(_tw.fq2_mul, _tw.fq2_square, _tw.fq2_mul_small, _g2_mul_by_b3,
                  _tw.FQ2_ZERO, _tw.FQ2_ONE, _tw.fq2_many)


def identity(ops: FieldOps, batch_shape=()):
    shape = batch_shape + ops.zero.shape
    return (
        jnp.broadcast_to(ops.zero, shape),
        jnp.broadcast_to(ops.one, shape),
        jnp.broadcast_to(ops.zero, shape),
    )


def point_add(ops: FieldOps, p, q):
    """Complete addition (RCB15 algorithm 7, a = 0).

    The 12 field muls run as TWO fused pipelines (a round of 6 independent
    products each) instead of 12 sequential ones — same operand rows, so
    the result limbs are bit-identical to the per-mul schedule.
    """
    x1, y1, z1 = p
    x2, y2, z2 = q
    b3 = ops.mul_by_b3
    (t0, t1, t2, t3, t4, x3), _ = ops.many(
        [(x1, x2), (y1, y2), (z1, z2),
         (x1 + y1, x2 + y2), (y1 + z1, y2 + z2), (x1 + z1, x2 + z2)])
    t3 = t3 - t0 - t1
    t4 = t4 - t1 - t2
    y3 = x3 - t0 - t2
    x3 = t0 + t0 + t0
    t2 = b3(t2)
    z3 = t1 + t2
    t1 = t1 - t2
    y3 = b3(y3)
    (m_t4y3, m_t3t1, m_y3x3, m_t1z3, m_x3t3, m_z3t4), _ = ops.many(
        [(t4, y3), (t3, t1), (y3, x3), (t1, z3), (x3, t3), (z3, t4)])
    return (m_t3t1 - m_t4y3, m_t1z3 + m_y3x3, m_z3t4 + m_x3t3)


def point_double(ops: FieldOps, p):
    """Complete doubling (RCB15 algorithm 9, a = 0) — 8 field products in
    THREE fused pipelines (bit-identical to the per-mul schedule)."""
    x, y, z = p
    b3 = ops.mul_by_b3
    (t1,), (t0, t2) = ops.many([(y, z)], [y, z])
    z3 = t0 + t0
    z3 = z3 + z3
    z3 = z3 + z3
    t2 = b3(t2)
    y3 = t0 + t2
    (x3, z3o, xy), _ = ops.many([(t2, z3), (t1, z3), (x, y)])
    t0 = t0 - (t2 + t2 + t2)
    (y3o, x3o), _ = ops.many([(t0, y3), (t0, xy)])
    return (x3o + x3o, x3 + y3o, z3o)


def point_neg(p):
    x, y, z = p
    return (x, -y, z)


def point_select(flag, p, q):
    """flag ? p : q, broadcasting flag (bool, batch shape) over coords."""
    def sel(a, b):
        f = flag.reshape(flag.shape + (1,) * (a.ndim - flag.ndim))
        return jnp.where(f, a, b)
    return tuple(sel(a, b) for a, b in zip(p, q))


def scalar_mul_bits(ops: FieldOps, p, bits):
    """[k]P with k given MSB-first as an int32 bit array (..., NBITS).

    Fixed-length left-to-right double-and-add with a select — constant-shape,
    no secret-dependent control flow (the weights here are verifier-chosen
    blinding scalars, not secrets, but uniformity is what vectorises).
    """
    nbits = bits.shape[-1]
    batch = bits.shape[:-1]
    acc = identity(ops, batch)

    def body(i, acc):
        acc = point_double(ops, acc)
        added = point_add(ops, acc, p)
        bit = bits[..., i].astype(bool)
        return point_select(bit, added, acc)

    return jax.lax.fori_loop(0, nbits, body, acc)


def _window_digits(bits: jax.Array, window: int) -> jax.Array:
    """MSB-first bit array (..., NBITS) -> MSB-first base-2^window digits
    (..., NBITS/window), each in [0, 2^window)."""
    nbits = bits.shape[-1]
    assert nbits % window == 0
    grouped = bits.reshape(*bits.shape[:-1], nbits // window, window)
    weights = jnp.asarray([1 << (window - 1 - j) for j in range(window)], jnp.int32)
    return jnp.einsum("...w,w->...", grouped, weights)


def _window_table(ops: FieldOps, p, window: int):
    """Stacked multiples [0]P..[2^w-1]P: tuple of (2^w, ...) coord arrays.
    One ``lax.scan`` of complete additions — a compact rolled graph (an
    unrolled chain multiplies compile time, the project's scarcest
    resource)."""
    size = 1 << window
    first = identity(ops, p[0].shape[: -ops.zero.ndim])

    def body(acc, _):
        return point_add(ops, acc, p), acc

    _, rows = jax.lax.scan(body, first, None, length=size)
    return rows  # tuple of (2^w, ...) stacked coords


def _table_select(table, digits: jax.Array):
    """table: (2^w, N, ...) coords; digits: (N,) -> selected (N, ...) points.
    One-hot einsum keeps the selection matmul-shaped (MXU) instead of a
    gather."""
    size = table[0].shape[0]
    onehot = (digits[:, None] == jnp.arange(size)[None, :]).astype(jnp.int32)

    def sel(c):  # c: (2^w, N, ...) -> (N, ...), per-set column selection
        return jnp.einsum("nd,dn...->n...", onehot, c,
                          preferred_element_type=jnp.int32)

    return tuple(sel(c) for c in table)


def scalar_mul_windowed(ops: FieldOps, p, bits, window: int = 4):
    """Per-set [k]P via fixed 2^w windows (VERDICT r3 item 2): a shared
    per-set multiples table + NBITS/w ladder steps of (w doublings + one
    table-select + one add) — ~25 % fewer group ops than double-and-add.
    Rolled as a ``lax.fori_loop`` so the graph stays small (doubling the
    identity on the first step is a harmless no-op)."""
    digits = _window_digits(bits, window)  # (N, S) MSB-first
    table = _window_table(ops, p, window)  # (2^w, N, ...)
    steps = digits.shape[-1]
    acc0 = identity(ops, bits.shape[:-1])

    def body(s, acc):
        for _ in range(window):
            acc = point_double(ops, acc)
        d = jax.lax.dynamic_index_in_dim(digits, s, axis=-1, keepdims=False)
        return point_add(ops, acc, _table_select(table, d))

    return jax.lax.fori_loop(0, steps, body, acc0)


def msm_windowed(ops: FieldOps, pts, bits, window: int = 4):
    """Multi-scalar multiplication sum_i [k_i] P_i with one SHARED doubling
    ladder (the batch-verification W = sum [r_i] sig_i collapses to this —
    blst.rs:112-114 computes the same sum point-by-point on CPU threads).

    Per ladder step: w doublings of ONE accumulator + a one-hot table
    select + a masked tree-sum across the batch — ~4x fewer group ops than
    per-set double-and-add followed by a tree-sum.  Rolled as a
    ``lax.fori_loop`` for compile-time economy."""
    n = pts[0].shape[0]
    if n & (n - 1):
        # Non-power-of-two batches arise only from mesh-divisibility padding
        # (a shrunk mesh of e.g. 7 devices pads 128 -> 133 rows): pad to the
        # next power of two with identity points + zero scalars — exact
        # neutral contributions, and power-of-two inputs keep the original
        # lowering untouched.
        pts = _pad_identity_rows(ops, pts, 0, n)
        pad = pts[0].shape[0] - n
        bits = jnp.concatenate(
            [bits, jnp.zeros((pad,) + bits.shape[1:], bits.dtype)], axis=0
        )
    digits = _window_digits(bits, window)  # (N, S)
    table = _window_table(ops, pts, window)  # (2^w, N, ...)
    steps = digits.shape[-1]
    acc0 = identity(ops)

    def body(s, acc):
        for _ in range(window):
            acc = point_double(ops, acc)
        d = jax.lax.dynamic_index_in_dim(digits, s, axis=-1, keepdims=False)
        contrib = _table_select(table, d)  # (N, ...) points
        return point_add(ops, acc, tree_sum(ops, contrib, axis=0))

    return jax.lax.fori_loop(0, steps, body, acc0)


def _pad_identity_rows(ops: FieldOps, pts, axis: int, n: int):
    """Grow ``axis`` from ``n`` to the next power of two with identity
    points (the group's exact neutral element — complete formulas absorb
    them with no special-casing)."""
    m = 1 << (n - 1).bit_length()
    ident = identity(ops)
    out = []
    for c, idc in zip(pts, ident):
        shape = list(c.shape)
        shape[axis] = m - n
        pad_block = jnp.broadcast_to(idc, tuple(shape))
        out.append(jnp.concatenate([c, pad_block], axis=axis))
    return tuple(out)


def tree_sum(ops: FieldOps, pts, axis: int = 0):
    """Sum points along a batch axis by halving rounds of complete additions.

    Power-of-two lengths take the original halving schedule untouched;
    other lengths (mesh-divisibility padding, e.g. 133 rows on a 7-device
    mesh) first pad with identity rows — exact neutral elements.
    """
    n = pts[0].shape[axis]
    if n & (n - 1):
        pts = _pad_identity_rows(ops, pts, axis, n)
        n = pts[0].shape[axis]
    while n > 1:
        half = n // 2

        def split(a):
            lo = jax.lax.slice_in_dim(a, 0, half, axis=axis)
            hi = jax.lax.slice_in_dim(a, half, n, axis=axis)
            return lo, hi

        lows, highs = zip(*(split(c) for c in pts))
        pts = point_add(ops, tuple(lows), tuple(highs))
        n = half
    return tuple(jnp.squeeze(c, axis=axis) for c in pts)


# ------------------------------------------------------- G2 cofactor clearing

_PSI_CX = _tw.fq2_to_limbs(_curve.PSI_CX)
_PSI_CY = _tw.fq2_to_limbs(_curve.PSI_CY)


def _x_abs_runs():
    """|x| MSB-first after its leading bit, as the doublings before each
    addition of the base point, then the doublings after the last one."""
    runs, n = [], 0
    for bit in bin(X_ABS)[3:]:
        n += 1
        if bit == "1":
            runs.append(n)
            n = 0
    return tuple(runs) + (n,)


#: (1, 2, 3, 9, 32, 16): 63 doublings; the base point is added after every
#: run but the last.
_X_ABS_RUNS = _x_abs_runs()


def g2_psi(p):
    """The endomorphism psi on projective G2 points:
    (cx·conj(X) : cy·conj(Y) : conj(Z)), ``curve.psi`` on each affine point."""
    x, y, z = p
    (px, py), _ = _tw.fq2_many([(_tw.fq2_conj(x), jnp.asarray(_PSI_CX)),
                                (_tw.fq2_conj(y), jnp.asarray(_PSI_CY))])
    return px, py, _tw.fq2_conj(z)


def _g2_mul_by_x_abs(p):
    """[|x|]P by the fixed double-and-add of |x| = 0xd201000000010000: a
    loop over its runs of doublings, each run a loop of its own length
    followed by one addition of P (of the identity after the last run), so
    no bit selects between an addition and none.  Two loop bodies keep the
    traced graph small."""
    runs = jnp.asarray(_X_ABS_RUNS, jnp.int32)
    last = len(_X_ABS_RUNS) - 1
    ident = identity(G2_OPS, p[0].shape[:-2])

    def double(_, acc):
        return point_double(G2_OPS, acc)

    def run(k, acc):
        acc = jax.lax.fori_loop(0, runs[k], double, acc)
        return point_add(G2_OPS, acc, point_select(k < last, p, ident))

    return jax.lax.fori_loop(0, last + 1, run, p)


def g2_clear_cofactor(p):
    """h_eff·P for projective points of E2, batched over leading axes: the
    value of ``curve.clear_cofactor_g2``, [x²−x−1]P + [x−1]psi(P) + psi²(2P),
    by the chain of RFC 9380 Appendix G.3 (``clear_cofactor_bls12381_g2``):
    t1 = [x]P, q = t1 + psi(P), t2 = [x]q, then t2 − q − P + psi²(2P).
    One loop body serves both multiplications by x, and its two additions
    run as one on a stacked axis, which keeps the traced graph small."""
    psi_p = g2_psi(p)

    def step(_, carry):  # q: P, then t1 + psi(P); acc: psi²(2P), less P, q
        q, acc, _ = carry
        r = point_neg(_g2_mul_by_x_abs(q))  # [x]q, x < 0
        both = point_add(G2_OPS, tuple(map(jnp.stack, zip(r, acc))),
                         tuple(map(jnp.stack, zip(psi_p, point_neg(q)))))
        return tuple(c[0] for c in both), tuple(c[1] for c in both), r

    acc0 = g2_psi(g2_psi(point_double(G2_OPS, p)))
    _, acc, t2 = jax.lax.fori_loop(0, 2, step, (p, acc0, p))
    return point_add(G2_OPS, acc, t2)


# ------------------------------------------------------------ host conversion


def g1_to_limbs(pt) -> tuple:
    """Host affine G1 point (golden-model Fq pair or None) -> projective limbs."""
    if pt is None:
        return (np.asarray(_fq.FQ_ZERO), np.asarray(_fq.FQ_ONE), np.asarray(_fq.FQ_ZERO))
    x, y = pt
    return (_fq.to_limbs16(x.n), _fq.to_limbs16(y.n), _fq.to_limbs16(1))


def g2_to_limbs(pt) -> tuple:
    if pt is None:
        return (np.asarray(_tw.FQ2_ZERO), np.asarray(_tw.FQ2_ONE), np.asarray(_tw.FQ2_ZERO))
    x, y = pt
    one = hf.Fq2(1, 0)
    return (_tw.fq2_to_limbs(x), _tw.fq2_to_limbs(y), _tw.fq2_to_limbs(one))


#: Bytes of one base-field element in limbs: 25 limbs of 16 bits.
_FQ_BYTES = 2 * _fq.L16


def _limbs_of_bytes(buf: bytes, shape: tuple) -> np.ndarray:
    """Joined 50-byte little-endian ints -> int32 limbs, ``shape + (25,)``:
    the ``to_limbs16`` of each.  ``int.to_bytes`` raised on a negative value
    or one past 400 bits, so nothing was silently cut."""
    return np.frombuffer(buf, "<u2").reshape(shape + (_fq.L16,)).astype(np.int32)


def g1_batch_to_limbs(points) -> tuple:
    """Host affine G1 points (none at infinity) -> (x, y), each (n, 25):
    the first two coordinates of ``g1_to_limbs`` of every point (z is 1).
    One pass over the points: a block's keys lie scattered in memory."""
    buf = b"".join([x.n.to_bytes(_FQ_BYTES, "little")
                    + y.n.to_bytes(_FQ_BYTES, "little") for x, y in points])
    xy = _limbs_of_bytes(buf, (-1, 2))
    return xy[:, 0], xy[:, 1]


def g2_batch_to_limbs(points) -> tuple:
    """Host affine G2 points (none at infinity) -> (x, y), each (n, 2, 25):
    the first two coordinates of ``g2_to_limbs`` of every point (z is 1)."""
    buf = b"".join([v.to_bytes(_FQ_BYTES, "little")
                    for pt in points for c in pt for v in (c.c0, c.c1)])
    xy = _limbs_of_bytes(buf, (-1, 2, 2))
    return xy[:, 0], xy[:, 1]


def g1_from_limbs(p):
    """Projective limbs -> host affine golden-model point (exact, host-side)."""
    x = _fq.from_limbs16(np.asarray(p[0]))
    y = _fq.from_limbs16(np.asarray(p[1]))
    z = _fq.from_limbs16(np.asarray(p[2]))
    if z == 0:
        return None
    zi = pow(z, P - 2, P)
    return (hf.Fq(x * zi % P), hf.Fq(y * zi % P))


def g2_from_limbs(p):
    x = _tw.fq2_from_limbs(np.asarray(p[0]))
    y = _tw.fq2_from_limbs(np.asarray(p[1]))
    z = _tw.fq2_from_limbs(np.asarray(p[2]))
    if z.is_zero():
        return None
    zi = z.inv()
    return (x * zi, y * zi)


def bits_msb(k: int, nbits: int) -> np.ndarray:
    return np.array([(k >> (nbits - 1 - i)) & 1 for i in range(nbits)], np.int32)


def bits_msb_batch(ks, nbits: int) -> np.ndarray:
    """``bits_msb(k, nbits)`` of every k in ``ks``, as rows of (n, nbits)."""
    nbytes = -(-nbits // 8)
    mask = (1 << nbits) - 1
    buf = b"".join([(k & mask).to_bytes(nbytes, "big") for k in ks])
    bits = np.unpackbits(np.frombuffer(buf, np.uint8).reshape(-1, nbytes), axis=1)
    return bits[:, 8 * nbytes - nbits:].astype(np.int32)


G1_GEN_LIMBS = g1_to_limbs((hf.Fq(G1_X), hf.Fq(G1_Y)))
G2_GEN_LIMBS = g2_to_limbs((hf.Fq2(G2_X_C0, G2_X_C1), hf.Fq2(G2_Y_C0, G2_Y_C1)))
