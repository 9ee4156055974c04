"""RFC 9380 hash-to-curve for BLS12-381 G2 (suite BLS12381G2_XMD:SHA-256_SSWU_RO_).

The reference client delegates this to blst via its DST constant
(``crypto/bls/src/impls/blst.rs:13``).  Here: expand_message_xmd(SHA-256) →
hash_to_field(Fp2, m=2, count=2, L=64) → simplified SWU on the 3-isogenous curve
E' → derived Velu isogeny (``_sswu_g2_iso.py``, see scripts/derive_g2_isogeny.py
for the derivation and the RFC-fingerprint cross-checks) → Budroni–Pintore
cofactor clearing.  ``hash_to_g2_uncleared_many`` takes the same steps up to
Q0 + Q1 for a batch of messages in a fraction of the modular exponentiations;
the block marshal (``ops/verify.build_batch``) hashes through it.
"""

from __future__ import annotations

import hashlib
import struct

from . import _sswu_g2_iso as ISO
from .curve import Point, add, clear_cofactor_g2
from .fields import Fq2
from .params import P, SSWU_A, SSWU_B, SSWU_Z

_A = Fq2(*SSWU_A)
_B = Fq2(*SSWU_B)
_Z = Fq2(*SSWU_Z)

_XNUM = [Fq2(c0, c1) for c0, c1 in ISO.XNUM]
_XDEN = [Fq2(c0, c1) for c0, c1 in ISO.XDEN]
_YNUM = [Fq2(c0, c1) for c0, c1 in ISO.YNUM]
_YDEN = [Fq2(c0, c1) for c0, c1 in ISO.YDEN]

L = 64  # ceil((ceil(log2(p)) + k) / 8) = ceil((381 + 128) / 8)


def expand_message_xmd(msg: bytes, dst: bytes, len_in_bytes: int) -> bytes:
    """RFC 9380 §5.3.1 with SHA-256."""
    b_in_bytes = 32
    r_in_bytes = 64
    ell = (len_in_bytes + b_in_bytes - 1) // b_in_bytes
    if ell > 255 or len_in_bytes > 65535 or len(dst) > 255:
        raise ValueError("expand_message_xmd parameter out of range")
    dst_prime = dst + bytes([len(dst)])
    z_pad = b"\x00" * r_in_bytes
    l_i_b_str = struct.pack(">H", len_in_bytes)
    b0 = hashlib.sha256(z_pad + msg + l_i_b_str + b"\x00" + dst_prime).digest()
    b1 = hashlib.sha256(b0 + b"\x01" + dst_prime).digest()
    bs = [b1]
    for i in range(2, ell + 1):
        prev = bs[-1]
        xored = bytes(a ^ b for a, b in zip(b0, prev))
        bs.append(hashlib.sha256(xored + bytes([i]) + dst_prime).digest())
    return b"".join(bs)[:len_in_bytes]


def hash_to_field_fq2(msg: bytes, count: int, dst: bytes) -> list:
    """RFC 9380 §5.2: count elements of Fp2."""
    m = 2
    uniform = expand_message_xmd(msg, dst, count * m * L)
    out = []
    for i in range(count):
        coeffs = []
        for j in range(m):
            off = L * (j + i * m)
            coeffs.append(int.from_bytes(uniform[off : off + L], "big") % P)
        out.append(Fq2(coeffs[0], coeffs[1]))
    return out


def map_to_curve_simple_swu(u: Fq2):
    """Simplified SWU map onto E': y^2 = x^3 + A'x + B' (RFC 9380 §6.6.2)."""
    u2 = u.square()
    zu2 = _Z * u2
    tv = zu2.square() + zu2  # Z^2 u^4 + Z u^2
    neg_b_over_a = -(_B * _A.inv())
    if tv.is_zero():
        x1 = _B * (_Z * _A).inv()
    else:
        x1 = neg_b_over_a * (Fq2.one() + tv.inv())
    gx1 = x1.square() * x1 + _A * x1 + _B
    y1 = gx1.sqrt()
    if y1 is not None:
        x, y = x1, y1
    else:
        x2 = zu2 * x1
        gx2 = x2.square() * x2 + _A * x2 + _B
        y2 = gx2.sqrt()
        assert y2 is not None, "SSWU: neither gx1 nor gx2 is square (impossible)"
        x, y = x2, y2
    if u.sgn0() != y.sgn0():
        y = -y
    return (x, y)


def _horner(poly, x: Fq2) -> Fq2:
    acc = Fq2.zero()
    for c in reversed(poly):
        acc = acc * x + c
    return acc


def iso_map(pt) -> Point:
    """Evaluate the 3-isogeny E' -> E2."""
    x, y = pt
    xden = _horner(_XDEN, x)
    if xden.is_zero():
        return None  # kernel point maps to infinity
    x2 = _horner(_XNUM, x) * xden.inv()
    y2 = y * _horner(_YNUM, x) * _horner(_YDEN, x).inv()
    return (x2, y2)


def hash_to_g2_uncleared(msg: bytes, dst: bytes) -> Point:
    """hash_to_curve up to Q0 + Q1: a point of E2 whose cofactor is not yet
    cleared (the device verifier clears it, ``ops/ec.g2_clear_cofactor``)."""
    u0, u1 = hash_to_field_fq2(msg, 2, dst)
    q0 = iso_map(map_to_curve_simple_swu(u0))
    q1 = iso_map(map_to_curve_simple_swu(u1))
    return add(q0, q1)


def hash_to_g2(msg: bytes, dst: bytes) -> Point:
    """hash_to_curve (random-oracle variant): the signing/verification H(m)."""
    return clear_cofactor_g2(hash_to_g2_uncleared(msg, dst))


# ------------------------------------------------- batched hash to Q0 + Q1
#
# The same RFC 9380 steps as ``hash_to_g2_uncleared``, for a whole batch of
# messages at once, in the fewest modular exponentiations (a 381-bit ``pow``
# costs CPython hundreds of times what a multiplication does):
#  - every inversion of one stage, across all lanes, shares one exponentiation
#    (Montgomery's trick over the Fq2 norms);
#  - an Fq2 square root costs two: s = N(a)^((p+1)/4) tests squareness and
#    roots the norm, t = d^((p-3)/4) roots d or -d and inverts that root;
#  - when gx1 is not square, gx2 = Z^3 u^6 gx1 and N(Z) = 5 is a non-residue,
#    so N(gx2)'s root is N(u)^3 sqrt(-125) s, with no exponentiation of its own.
# Per message: two roots of two exponentiations each; per batch: one for each
# of the three inversion stages (tv, isogeny denominators, Q0 + Q1 slopes).
# A lane that meets an exceptional case (tv = 0, a zero isogeny denominator,
# Q0.x == Q1.x) is hashed by ``hash_to_g2_uncleared`` instead.

_INV2 = (P + 1) // 2
_NEG_B_OVER_A = -(_B * _A.inv())
_SQRT_M125 = pow(P - 125, (P + 1) // 4, P)
assert _SQRT_M125 * _SQRT_M125 % P == P - 125


def _norm(a: Fq2) -> int:
    return (a.c0 * a.c0 + a.c1 * a.c1) % P


def _batch_inv(elems: list) -> list:
    """The inverses of nonzero Fq2 elements for one exponentiation:
    1/a = conj(a) / N(a), the norms inverted by Montgomery's trick."""
    norms = [_norm(a) for a in elems]
    prefix = []
    acc = 1
    for n in norms:
        prefix.append(acc)
        acc = acc * n % P
    inv = pow(acc, P - 2, P)
    out = [None] * len(elems)
    for i in range(len(elems) - 1, -1, -1):
        ni = inv * prefix[i] % P
        inv = inv * norms[i] % P
        out[i] = Fq2(elems[i].c0 * ni, -elems[i].c1 * ni)
    return out


def _sqrt_with_norm_root(a: Fq2, s: int) -> Fq2:
    """A square root of ``a`` given s with s^2 = N(a), for one exponentiation.

    d = (a0 + s)/2 and d' = (a0 - s)/2 multiply to -a1^2/4, so exactly one
    is square when a1 != 0.  t = d^((p-3)/4) gives chi(d) = d t^2; if d is
    square the root is (d t, (a1/2) t), else -d is, d t is its root, and
    the root is (-(a1/2) t, d t).  When a1 = 0 and s = -a0, d is 0 and d'
    = a0 takes its place.  The root equals ``Fq2.sqrt``'s up to sign."""
    a0, a1 = a.c0, a.c1
    d = (a0 + s) * _INV2 % P
    if d == 0:
        d = a0
    t = pow(d, (P - 3) // 4, P)
    h = a1 * _INV2 % P
    if d * t % P * t % P == 1:
        return Fq2(d * t, h * t)
    return Fq2(-h * t, d * t)


def _sqrt(a: Fq2):
    """A square root of ``a`` (``Fq2.sqrt``'s up to sign), or None, for two
    exponentiations; and s = N(a)^((p+1)/4), which squares to N(a) where
    ``a`` is square and to -N(a) where it is not."""
    n = _norm(a)
    s = pow(n, (P + 1) // 4, P)
    if s * s % P != n:
        return None, s
    return _sqrt_with_norm_root(a, s), s


def _sswu_root(u: Fq2, zu2: Fq2, x1: Fq2):
    """The SSWU point (x, y) of u from x1, y's sign not yet fixed: two
    exponentiations in either branch."""
    gx1 = (x1.square() + _A) * x1 + _B
    y, s = _sqrt(gx1)
    if y is not None:
        return x1, y
    # s^2 = -N(gx1), so (N(u)^3 sqrt(-125) s)^2 = 125 N(u)^6 N(gx1) = N(gx2).
    x2 = zu2 * x1
    gx2 = (x2.square() + _A) * x2 + _B
    nu = _norm(u)
    s2 = nu * nu % P * nu % P * _SQRT_M125 % P * s % P
    return x2, _sqrt_with_norm_root(gx2, s2)


def hash_to_g2_uncleared_many(msgs: list, dst: bytes) -> tuple[list[Point], int]:
    """``hash_to_g2_uncleared`` of every message, batched: returns the same
    points in the same order, and the modular exponentiations the batched
    stages spent (4 per message + 3 per batch; a lane routed to the
    per-message path is not counted)."""
    if not msgs:
        return [], 0
    us = [hash_to_field_fq2(m, 2, dst) for m in msgs]
    zu2 = [[_Z * u.square() for u in pair] for pair in us]
    tv = [[z.square() + z for z in pair] for pair in zu2]
    golden = {i for i, pair in enumerate(tv) if any(t.is_zero() for t in pair)}
    lanes = [i for i in range(len(msgs)) if i not in golden]
    modexp = 3 + 4 * len(lanes)

    # SSWU onto E', both u of each lane: one inversion for every tv.
    tv_inv = iter(_batch_inv([t for i in lanes for t in tv[i]]))
    pts = {}
    for i in lanes:
        pts[i] = []
        for u, z in zip(us[i], zu2[i]):
            x, y = _sswu_root(u, z, _NEG_B_OVER_A * (Fq2.one() + next(tv_inv)))
            pts[i].append((x, -y if u.sgn0() != y.sgn0() else y))

    # The isogeny E' -> E2: one inversion for every denominator.  A point
    # of its kernel (a zero denominator) maps to infinity.
    dens = {i: [d for x, _ in pts[i] for d in (_horner(_XDEN, x), _horner(_YDEN, x))]
            for i in lanes}
    golden.update(i for i in lanes if any(d.is_zero() for d in dens[i]))
    lanes = [i for i in lanes if i not in golden]
    den_inv = iter(_batch_inv([d for i in lanes for d in dens[i]]))
    qs = {i: [(_horner(_XNUM, x) * next(den_inv), y * _horner(_YNUM, x) * next(den_inv))
              for x, y in pts[i]] for i in lanes}

    # Q0 + Q1 by the affine chord: one inversion for every slope.  Equal x
    # (a doubling, or Q1 = -Q0) takes the per-message path.
    golden.update(i for i in lanes if qs[i][0][0] == qs[i][1][0])
    lanes = [i for i in lanes if i not in golden]
    out = [None] * len(msgs)
    slope_inv = _batch_inv([qs[i][1][0] - qs[i][0][0] for i in lanes])
    for i, inv in zip(lanes, slope_inv):
        (x0, y0), (x1, y1) = qs[i]
        m = (y1 - y0) * inv
        x3 = m * m - x0 - x1
        out[i] = (x3, m * (x0 - x3) - y0)
    for i in golden:
        out[i] = hash_to_g2_uncleared(msgs[i], dst)
    return out, modexp
