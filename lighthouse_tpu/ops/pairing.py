"""Batched optimal-ate multi-pairing on TPU (JAX), inversion-free Miller loop.

Mirrors ``crypto/bls/host_projective.py`` (the host-integer oracle) over limb
arrays: projective Miller loop on the twist with denominator elimination, fixed
63-step ``lax.scan`` over the BLS parameter bits, shared final exponentiation.
This program occupies the slot of blst's ``verify_multiple_aggregate_signatures``
multi-pairing core (reference ``crypto/bls/src/impls/blst.rs:112-114``).

G1 arguments are *projective* — the line value is scaled by Z_P, which lies in
Fp and is erased by the final exponentiation, so scalar-multiplication outputs
feed the Miller loop with no inversion anywhere.  G2 infinity (degenerate twist
point) must be masked by the caller (``mask`` argument): unlike G1 infinity
(which contributes only subfield factors, auto-killed by the final exp), a
Z=0 twist point collapses the accumulator to zero.

All functions broadcast over leading batch dims; the scan carries batched state.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto.bls.params import X_ABS
from . import tower as tw
from .tower import (
    FQ12_ONE,
    FQ2_ZERO,
    fq2_mul,
    fq2_mul_by_xi,
    fq2_mul_small,
    fq2_sub,
    fq12_conj,
    fq12_frobenius,
    fq12_frobenius_n,
    fq12_inv,
    fq12_mul,
    fq12_square,
)

# Miller schedule: bits of |x| below the leading one, MSB first (63 steps).
_X_BITS = jnp.asarray([int(b) for b in bin(X_ABS)[3:]], dtype=jnp.int32)
# pow_x schedule: bits of |x|, LSB first (64 steps).
_X_BITS_LSB = jnp.asarray([(X_ABS >> i) & 1 for i in range(X_ABS.bit_length())], jnp.int32)


def _proj_dbl(t):
    """Twist-point doubling + eliminated-denominator line (host_projective.proj_dbl).

    The 16 Fq2 products run as THREE fused pipelines (tw.fq2_many) instead
    of 16 sequential conv+reduce round-trips — same sub-product operand
    rows, so the outputs are bit-identical to the per-call schedule.
    """
    x, y, z = t
    (xy, s), (xx, y2, zz) = tw.fq2_many([(x, y), (y, z)], [x, y, z])
    w3 = fq2_mul_small(xx, 3)
    (b, ys, yzz, xx3x, xxz, y2_2z), (w3sq, s2) = tw.fq2_many(
        [(xy, s), (y, s), (y, zz), (xx, fq2_mul_small(x, 3)), (xx, z),
         (y2, fq2_mul_small(z, 2))],
        [w3, s],
    )
    h = fq2_sub(w3sq, fq2_mul_small(b, 8))
    (hs, tt, s3), (y2s2,) = tw.fq2_many(
        [(h, s), (w3, fq2_mul_small(b, 4) - h), (s2, s)], [ys]
    )
    x3 = fq2_mul_small(hs, 2)
    y3 = fq2_sub(tt, fq2_mul_small(y2s2, 8))
    z3 = fq2_mul_small(s3, 8)

    l00 = fq2_mul_by_xi(fq2_mul_small(yzz, 2))
    l1v = -(y2_2z - xx3x)
    l1vv = -fq2_mul_small(xxz, 3)
    return (x3, y3, z3), (l00, l1v, l1vv)


def _proj_add_mixed(t, q):
    """Mixed addition + line (host_projective.proj_add_mixed) — 14 Fq2
    products in FOUR fused pipelines, bit-identical to the per-call form."""
    x, y, z = t
    xq, yq = q
    (yqz, xqz), _ = tw.fq2_many([(yq, z), (xq, z)])
    e = fq2_sub(yqz, y)
    f = fq2_sub(xqz, x)
    (yqf, exq), (ff, ee) = tw.fq2_many([(yq, f), (e, xq)], [f, e])
    (fff, eez, ffx, ffs), _ = tw.fq2_many(
        [(f, ff), (ee, z), (ff, x), (ff, x + xqz)]
    )
    t1 = fq2_sub(eez, ffs)
    (x3, et, fffy, z3), _ = tw.fq2_many(
        [(f, t1), (e, fq2_sub(ffx, t1)), (fff, y), (z, fff)]
    )
    y3 = fq2_sub(et, fffy)

    l00 = fq2_mul_by_xi(f)
    l1v = -fq2_sub(yqf, exq)
    l1vv = -e
    return (x3, y3, z3), (l00, l1v, l1vv)


def _line_fq12(line, p1):
    """Assemble sparse line * Z_P-scaling into a full Fq12 element.

    l = (L00*Y_P) + w*( (L1v*Z_P)*v + (L1vv*X_P)*v^2 )  — see module docstring.
    """
    l00, l1v, l1vv = line
    xp, yp, zp = p1
    a, b1, b2 = tw.fq2_mul_fq_many([(l00, yp), (l1v, zp), (l1vv, xp)])
    zero = jnp.broadcast_to(FQ2_ZERO, l00.shape)
    c0 = jnp.stack([a, zero, zero], axis=-3)
    c1 = jnp.stack([zero, b1, b2], axis=-3)
    return jnp.stack([c0, c1], axis=-4)


def miller_loop(p1, q2):
    """f_{|x|,Q}(P) for batched projective G1 p1=(X,Y,Z) and affine twist q2=(x,y).

    Returns batched Fq12 (leading dims = broadcast of input batch dims).

    The BLS parameter has Hamming weight 6, so 58 of the 63 scan steps take
    only the doubling path; the mixed-addition step runs under ``lax.cond``
    (a real XLA conditional — the untaken branch costs nothing at runtime,
    unlike the former compute-both-and-select).
    """
    xq, yq = q2
    t0 = (xq, yq, jnp.broadcast_to(tw.FQ2_ONE, xq.shape))
    batch = jnp.broadcast_shapes(p1[0].shape[:-1], xq.shape[:-2])
    f0 = jnp.broadcast_to(FQ12_ONE, batch + FQ12_ONE.shape)

    def do_add(ft):
        f, t = ft
        t_a, line_a = _proj_add_mixed(t, q2)
        return fq12_mul(f, _line_fq12(line_a, p1)), t_a

    def body(carry, bit):
        f, t = carry
        t, line = _proj_dbl(t)
        f = fq12_mul(fq12_square(f), _line_fq12(line, p1))
        f, t = jax.lax.cond(bit.astype(bool), do_add, lambda ft: ft, (f, t))
        return (f, t), None

    (f, _), _ = jax.lax.scan(body, (f0, t0), _X_BITS)
    return f


def _pow_x(g):
    """g^|x| then conjugate (x < 0), for g in the cyclotomic subgroup.

    Same static-Hamming-weight trick as the Miller loop: the multiply fires
    under ``lax.cond`` on only 6 of 64 steps."""

    def body(carry, bit):
        r, b = carry
        r = jax.lax.cond(
            bit.astype(bool), lambda rb: fq12_mul(rb[0], rb[1]), lambda rb: rb[0], (r, b)
        )
        b = fq12_square(b)
        return (r, b), None

    one = jnp.broadcast_to(FQ12_ONE, g.shape)
    (r, _), _ = jax.lax.scan(body, (one, g), _X_BITS_LSB)
    return fq12_conj(r)


def final_exponentiation(f):
    """Mirror of the golden model's f^((p^12-1)/r * 3) (pairing.py:75-90)."""
    f = fq12_mul(fq12_conj(f), fq12_inv(f))        # ^(p^6 - 1)
    f = fq12_mul(fq12_frobenius_n(f, 2), f)        # ^(p^2 + 1)
    t0 = fq12_mul(_pow_x(f), fq12_conj(f))
    t1 = fq12_mul(_pow_x(t0), fq12_conj(t0))
    t2 = fq12_mul(_pow_x(t1), fq12_frobenius(t1))
    t3 = fq12_mul(fq12_mul(_pow_x(_pow_x(t2)), fq12_frobenius_n(t2, 2)), fq12_conj(t2))
    f3 = fq12_mul(fq12_mul(f, f), f)
    return fq12_mul(t3, f3)


def fq12_product(fs, axis: int = 0):
    """Multiplicative tree-reduce along a batch axis (power-of-two length)."""
    n = fs.shape[axis]
    assert n & (n - 1) == 0, "fq12_product requires power-of-two length"
    while n > 1:
        half = n // 2
        lo = jax.lax.slice_in_dim(fs, 0, half, axis=axis)
        hi = jax.lax.slice_in_dim(fs, half, n, axis=axis)
        fs = fq12_mul(lo, hi)
        n = half
    return jnp.squeeze(fs, axis=axis)


def fq12_product_any(fs, axis: int = 0):
    """Multiplicative tree-reduce along a batch axis, any length >= 1.

    Odd tails are set aside and folded back at the end — no neutral-element
    padding muls (a 129-long product costs 128 muls, not 255)."""
    n = fs.shape[axis]
    extra = None
    while n > 1:
        if n % 2:
            last = jax.lax.slice_in_dim(fs, n - 1, n, axis=axis)
            extra = last if extra is None else fq12_mul(extra, last)
            n -= 1
            fs = jax.lax.slice_in_dim(fs, 0, n, axis=axis)
        half = n // 2
        fs = fq12_mul(
            jax.lax.slice_in_dim(fs, 0, half, axis=axis),
            jax.lax.slice_in_dim(fs, half, n, axis=axis),
        )
        n = half
    if extra is not None:
        fs = fq12_mul(fs, extra)
    return jnp.squeeze(fs, axis=axis)


# ------------------------------------------------- sparse-line multi-pairing

# A line in sparse form is three Fq2 coefficients (a, b1, b2) representing the
# Fq12 element (a + 0 v + 0 v^2) + (0 + b1 v + b2 v^2) w  — see _line_fq12.


def _sparse_line_coeffs(line, p1, mask):
    """Scale a raw line by the projective G1 coords and mask dead pairs to 1."""
    l00, l1v, l1vv = line
    xp, yp, zp = p1
    a, b1, b2 = tw.fq2_mul_fq_many([(l00, yp), (l1v, zp), (l1vv, xp)])
    m = mask.reshape(mask.shape + (1, 1))
    one = jnp.broadcast_to(tw.FQ2_ONE, a.shape)
    return jnp.where(m, a, one), jnp.where(m, b1, 0), jnp.where(m, b2, 0)


def _sparse_to_fq12(a, b1, b2):
    """Expand sparse line coefficients to a full Fq12 element."""
    zero = jnp.zeros_like(a)
    c0 = jnp.stack([a, zero, zero], axis=-3)
    c1 = jnp.stack([zero, b1, b2], axis=-3)
    return jnp.stack([c0, c1], axis=-4)


def _sparse_pair_mul(x, y):
    """Product of two sparse lines -> full Fq12, 9 fq2 muls (vs 18 general).

    (A + B w)(C + D w) = (AC + v BD) + (AD + CB) w with A=(a,0,0), B=(0,b1,b2):
        c0 = (ac + xi*b1d1,  xi*(b1d2 + b2d1),  xi*b2d2)
        c1 = (0,  a d1 + c b1,  a d2 + c b2)
    """
    a, b1, b2 = x
    c, d1, d2 = y
    lhs = jnp.stack([a, b1, b1, b2, b2, a, c, a, c], axis=-3)
    rhs = jnp.stack([c, d1, d2, d1, d2, d1, b1, d2, b2], axis=-3)
    p = fq2_mul(lhs, rhs)
    p0, p1_, p2, p3, p4 = (p[..., i, :, :] for i in range(5))
    p5, p6, p7, p8 = (p[..., i, :, :] for i in range(5, 9))
    zero = jnp.zeros_like(p0)
    c0 = jnp.stack(
        [p0 + fq2_mul_by_xi(p1_), fq2_mul_by_xi(p2 + p3), fq2_mul_by_xi(p4)], axis=-3
    )
    c1 = jnp.stack([zero, p5 + p6, p7 + p8], axis=-3)
    return jnp.stack([c0, c1], axis=-4)


def _lines_product(a, b1, b2):
    """Product of N sparse lines (leading axis) -> one full Fq12.

    First level pairs sparse x sparse (half-cost); upper levels are a general
    tree product with no padding waste."""
    n = a.shape[0]
    if n == 1:
        return jnp.squeeze(_sparse_to_fq12(a, b1, b2), axis=0)
    h = n // 2
    lo = (a[:h], b1[:h], b2[:h])
    hi = (a[h : 2 * h], b1[h : 2 * h], b2[h : 2 * h])
    prod = _sparse_pair_mul(lo, hi)
    if n % 2:
        prod = jnp.concatenate(
            [prod, _sparse_to_fq12(a[-1:], b1[-1:], b2[-1:])], axis=0
        )
    return fq12_product_any(prod)


def multi_pairing_fe(p1, q2, mask):
    """FE(prod_i f_i) over the leading pair axis, with per-pair live mask.

    p1: projective G1, coords (N, 25); q2: affine twist, coords (N, 2, 25);
    mask: (N,) bool — False pairs contribute the neutral element (required for
    G2 infinity, used for padding).

    Shared-accumulator multi-Miller (the big r5 kernel win): the T points and
    line computations stay batched per pair, but the Fq12 accumulator is ONE
    element — per step, f = f^2 * prod_i line_i.  This removes the per-pair
    f^2 (N full squarings/step) and replaces N+1 accumulator muls with an
    N-mul tree whose first level multiplies sparse x sparse lines at half
    cost.  Same algebra as the per-pair loop (multiplication mod p is
    commutative/associative), so the FE output value is bit-identical.
    """
    xq, yq = q2
    t0 = (xq, yq, jnp.broadcast_to(tw.FQ2_ONE, xq.shape))
    f0 = FQ12_ONE

    def fold_lines(f, line):
        a, b1, b2 = _sparse_line_coeffs(line, p1, mask)
        return fq12_mul(f, _lines_product(a, b1, b2))

    def do_add(ft):
        f, t = ft
        t_a, line_a = _proj_add_mixed(t, q2)
        return fold_lines(f, line_a), t_a

    def body(carry, bit):
        f, t = carry
        t, line = _proj_dbl(t)
        f = fold_lines(fq12_square(f), line)
        f, t = jax.lax.cond(bit.astype(bool), do_add, lambda ft: ft, (f, t))
        return (f, t), None

    # Named scopes (HLO metadata only) for device_telemetry.program_stages:
    # every program that pairs shares these two stages.
    with jax.named_scope("miller_loop"):
        (f, _), _ = jax.lax.scan(body, (f0, t0), _X_BITS)
    with jax.named_scope("final_exp"):
        return final_exponentiation(f)


# ------------------------------------------------------------ host-side check


def fe_is_one(fe_limbs) -> bool:
    """Exact host check that a final-exponentiation output equals 1."""
    val = tw.fq12_from_limbs(np.asarray(fe_limbs))
    return val.is_one()
