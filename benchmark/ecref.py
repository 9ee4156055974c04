"""The benchmark's own BLS12-381 arithmetic over plain Python integers.

It is the yardstick's half of every signature and blob: the traffic
generator signs and commits with it, and a set's expected verdict follows
from how it was made.  It imports nothing of the program (``lighthouse_tpu``),
so a fault in the program's hash-to-curve or field code cannot make a wrong
verdict look right.  ``benchmark/tests`` checks it against the program's host
golden model, which is itself checked against the EF vectors.

Conventions: Fp elements are ints in ``[0, P)``; Fp2 elements are pairs
``(c0, c1)`` meaning ``c0 + c1*u`` with ``u^2 = -1``; a Jacobian point is
``(X, Y, Z)`` standing for ``(X/Z^2, Y/Z^3)``; ``None`` is the point at
infinity.  Formulas: EFD dbl-2009-l and add-2007-bl (``a = 0``).
"""

from __future__ import annotations

import hashlib
import struct

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X_PARAM = -0xD201000000010000
G1 = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
G2 = (
    (0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
     0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
    (0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
     0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE),
)
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


# ------------------------------------------------------------------ Fp, Fp2

def inv(a: int) -> int:
    return pow(a, -1, P)


def f2add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2neg(a):
    return (-a[0] % P, -a[1] % P)


def f2mul(a, b):
    t0 = a[0] * b[0]
    t1 = a[1] * b[1]
    return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)


def f2sqr(a):
    return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)


def f2inv(a):
    d = inv((a[0] * a[0] + a[1] * a[1]) % P)
    return (a[0] * d % P, -a[1] * d % P)


def f2conj(a):
    return (a[0], -a[1] % P)


def f2pow(a, e: int):
    out = (1, 0)
    while e:
        if e & 1:
            out = f2mul(out, a)
        a = f2sqr(a)
        e >>= 1
    return out


def _fp_sqrt(a: int):
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a else None


def f2sqrt(a):
    """A square root of ``a`` (complex method, p = 3 mod 4), or None."""
    a0, a1 = a
    if a1 == 0:
        s = _fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        s = _fp_sqrt(-a0 % P)
        return None if s is None else (0, s)
    n = _fp_sqrt((a0 * a0 + a1 * a1) % P)
    if n is None:
        return None
    half = (P + 1) // 2
    x = _fp_sqrt((a0 + n) * half % P)
    if x is None:
        x = _fp_sqrt((a0 - n) * half % P)
        if x is None:
            return None
    y = a1 * half % P * inv(x) % P
    return (x, y) if f2sqr((x, y)) == (a0 % P, a1 % P) else None


def f2sgn0(a) -> int:
    return (a[0] & 1) | (int(a[0] == 0) & (a[1] & 1))


# ------------------------------------------------- Jacobian points, generic

class _F1:
    zero, one = 0, 1
    add = staticmethod(lambda a, b: (a + b) % P)
    sub = staticmethod(lambda a, b: (a - b) % P)
    mul = staticmethod(lambda a, b: a * b % P)
    sqr = staticmethod(lambda a: a * a % P)
    neg = staticmethod(lambda a: -a % P)
    inv = staticmethod(inv)


class _F2:
    zero, one = (0, 0), (1, 0)
    add, sub, mul, sqr, neg, inv = (staticmethod(f) for f in
                                    (f2add, f2sub, f2mul, f2sqr, f2neg, f2inv))


def _dbl(F, p):
    if p is None:
        return None
    X1, Y1, Z1 = p
    if Y1 == F.zero:
        return None
    A = F.sqr(X1)
    B = F.sqr(Y1)
    C = F.sqr(B)
    D = F.sub(F.sub(F.sqr(F.add(X1, B)), A), C)
    D = F.add(D, D)
    E = F.add(F.add(A, A), A)
    X3 = F.sub(F.sqr(E), F.add(D, D))
    C8 = F.add(C, C)
    C8 = F.add(C8, C8)
    C8 = F.add(C8, C8)
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), C8)
    Z3 = F.mul(F.add(Y1, Y1), Z1)
    return (X3, Y3, Z3)


def _add(F, p, q):
    if p is None:
        return q
    if q is None:
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = F.sqr(Z1)
    Z2Z2 = F.sqr(Z2)
    U1 = F.mul(X1, Z2Z2)
    U2 = F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    if U1 == U2:
        return _dbl(F, p) if S1 == S2 else None
    H = F.sub(U2, U1)
    I = F.sqr(F.add(H, H))
    J = F.mul(H, I)
    r = F.sub(S2, S1)
    r = F.add(r, r)
    V = F.mul(U1, I)
    X3 = F.sub(F.sub(F.sqr(r), J), F.add(V, V))
    S1J = F.mul(S1, J)
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.add(S1J, S1J))
    Z3 = F.mul(F.sub(F.sub(F.sqr(F.add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def _neg(F, p):
    return None if p is None else (p[0], F.neg(p[1]), p[2])


def _mul(F, p, k: int):
    """[k]p, left-to-right double-and-add."""
    if k < 0:
        return _mul(F, _neg(F, p), -k)
    acc = None
    for bit in bin(k)[2:] if k else "":
        acc = _dbl(F, acc)
        if bit == "1":
            acc = _add(F, acc, p)
    return acc


def _affine(F, p):
    if p is None:
        return None
    zi = F.inv(p[2])
    zi2 = F.sqr(zi)
    return (F.mul(p[0], zi2), F.mul(p[1], F.mul(zi2, zi)))


def _jac(F, a):
    return None if a is None else (a[0], a[1], F.one)


def g1_mul(pt, k: int):
    """[k]pt for an affine G1 point; affine result."""
    return _affine(_F1, _mul(_F1, _jac(_F1, pt), k))


def g2_mul(pt, k: int):
    return _affine(_F2, _mul(_F2, _jac(_F2, pt), k))


def g1_chain(start: int, count: int, lanes: int = 256):
    """Affine ``[start + i]G1`` for ``i < count``, by successive addition of
    G1 along ``lanes`` interleaved chains with one shared inversion per step
    (Montgomery's trick).  About six multiplications a key."""
    lanes = max(1, min(lanes, count))
    per = -(-count // lanes)
    cur = [g1_mul(G1, start + j * per) for j in range(lanes)]
    cols = [[c] for c in cur]
    gx, gy = G1
    for _ in range(per - 1):
        den = [(gx - x) % P for x, _ in cur]
        prefix = [1] * (lanes + 1)
        for j, d in enumerate(den):
            prefix[j + 1] = prefix[j] * d % P
        acc = inv(prefix[lanes])
        invs = [0] * lanes
        for j in range(lanes - 1, -1, -1):
            invs[j] = prefix[j] * acc % P
            acc = acc * den[j] % P
        nxt = []
        for (x, y), di in zip(cur, invs):
            lam = (gy - y) * di % P
            x3 = (lam * lam - x - gx) % P
            nxt.append((x3, (lam * (x - x3) - y) % P))
        cur = nxt
        for j, c in enumerate(cur):
            cols[j].append(c)
    return [pt for col in cols for pt in col][:count]


def g1_compress(pt) -> bytes:
    """ZCash 48-byte compressed encoding of an affine G1 point."""
    if pt is None:
        return bytes([0xC0]) + b"\x00" * 47
    x, y = pt
    flags = 0x80 | (0x20 if y > (P - 1) // 2 else 0)
    raw = x.to_bytes(48, "big")
    return bytes([raw[0] | flags]) + raw[1:]


# ------------------------------------------- RFC 9380 hash-to-curve for G2

_SSWU_A = (0, 240)
_SSWU_B = (1012, 1012)
_SSWU_Z = (P - 2, P - 1)
_ISO = {
    "xnum": [(0x5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97d6, 0x5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97d6), (0x0, 0x11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71a), (0x11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71e, 0x8ab05f8bdd54cde190937e76bc3e447cc27c3d6fbd7063fcd104635a790520c0a395554e5c6aaaa9354ffffffffe38d), (0x171d6541fa38ccfaed6dea691f5fb614cb14b4e7f4e810aa22d6108f142b85757098e38d0f671c7188e2aaaaaaaa5ed1, 0x0)],
    "xden": [(0x0, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa63), (0xc, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa9f), (0x1, 0x0)],
    "ynum": [(0x1530477c7ab4113b59a4c18b076d11930f7da5d4a07f649bf54439d87d27e500fc8c25ebf8c92f6812cfc71c71c6d706, 0x1530477c7ab4113b59a4c18b076d11930f7da5d4a07f649bf54439d87d27e500fc8c25ebf8c92f6812cfc71c71c6d706), (0x0, 0x5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97be), (0x11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71c, 0x8ab05f8bdd54cde190937e76bc3e447cc27c3d6fbd7063fcd104635a790520c0a395554e5c6aaaa9354ffffffffe38f), (0x124c9ad43b6cf79bfbf7043de3811ad0761b0f37a1e26286b0e977c69aa274524e79097a56dc4bd9e1b371c71c718b10, 0x0)],
    "yden": [(0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa8fb, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa8fb), (0x0, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa9d3), (0x12, 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa99), (0x1, 0x0)],
}
_XI = (1, 1)
_PSI_CX = f2inv(f2pow(_XI, (P - 1) // 3))
_PSI_CY = f2inv(f2pow(_XI, (P - 1) // 2))


def expand_message_xmd(msg: bytes, dst: bytes, length: int) -> bytes:
    ell = -(-length // 32)
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(b"\x00" * 64 + msg + struct.pack(">H", length)
                        + b"\x00" + dst_prime).digest()
    bs = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, bs[-1]))
        bs.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(bs)[:length]


def _hash_to_fp2(msg: bytes, dst: bytes):
    u = expand_message_xmd(msg, dst, 256)
    e = [int.from_bytes(u[64 * i:64 * (i + 1)], "big") % P for i in range(4)]
    return (e[0], e[1]), (e[2], e[3])


def _sswu(u):
    zu2 = f2mul(_SSWU_Z, f2sqr(u))
    tv = f2add(f2sqr(zu2), zu2)
    if tv == (0, 0):
        x1 = f2mul(_SSWU_B, f2inv(f2mul(_SSWU_Z, _SSWU_A)))
    else:
        x1 = f2mul(f2neg(f2mul(_SSWU_B, f2inv(_SSWU_A))),
                   f2add((1, 0), f2inv(tv)))

    def g(x):
        return f2add(f2add(f2mul(f2sqr(x), x), f2mul(_SSWU_A, x)), _SSWU_B)

    x, y = x1, f2sqrt(g(x1))
    if y is None:
        x = f2mul(zu2, x1)
        y = f2sqrt(g(x))
    if f2sgn0(u) != f2sgn0(y):
        y = f2neg(y)
    return x, y


def _horner(coeffs, x):
    acc = (0, 0)
    for c in reversed(coeffs):
        acc = f2add(f2mul(acc, x), c)
    return acc


def _iso(pt):
    x, y = pt
    return (f2mul(_horner(_ISO["xnum"], x), f2inv(_horner(_ISO["xden"], x))),
            f2mul(y, f2mul(_horner(_ISO["ynum"], x),
                           f2inv(_horner(_ISO["yden"], x)))))


def _psi(p):
    if p is None:
        return None
    X, Y, Z = p
    return (f2mul(_PSI_CX, f2conj(X)), f2mul(_PSI_CY, f2conj(Y)), f2conj(Z))


def _clear_cofactor(p):
    """Budroni-Pintore: [x^2 - x - 1]P + [x - 1]psi(P) + psi^2([2]P)."""
    F = _F2
    xp = _mul(F, p, X_PARAM)
    x2p = _mul(F, xp, X_PARAM)
    t1 = _add(F, _add(F, x2p, _neg(F, xp)), _neg(F, p))
    t2 = _add(F, _psi(xp), _neg(F, _psi(p)))
    t3 = _psi(_psi(_dbl(F, p)))
    return _add(F, _add(F, t1, t2), t3)


def hash_to_g2_jac(msg: bytes, dst: bytes = DST):
    u0, u1 = _hash_to_fp2(msg, dst)
    q = _add(_F2, _jac(_F2, _iso(_sswu(u0))), _jac(_F2, _iso(_sswu(u1))))
    return _clear_cofactor(q)


def hash_to_g2(msg: bytes, dst: bytes = DST):
    """H(m) as an affine pair of Fp2 pairs."""
    return _affine(_F2, hash_to_g2_jac(msg, dst))


def g2_jac_mul(pj, k: int):
    """``[k]P`` for a Jacobian G2 point, affine."""
    return _affine(_F2, _mul(_F2, pj, k))


def sign_with(msg: bytes, k: int):
    """The signature ``[k]H(msg)`` of a key whose scalar is ``k``, affine."""
    return g2_jac_mul(hash_to_g2_jac(msg), k)
