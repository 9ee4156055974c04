"""Work of one batch verification of signature sets (``bls_verify``).

Counted from the LIVE request, not the program's padded bucket: ``n`` sets
holding ``keys`` public keys in all.  The textbook algorithm
(``blst.rs`` verify_signature_sets) needs:

- ``keys - n`` G1 additions to aggregate each set's keys;
- ``n`` 64-bit G1 ladders for the random weights;
- one 64-bit G2 multi-scalar multiplication over ``n`` points, its
  doublings shared (``W = sum r_i sig_i``), and W to affine;
- ``n + 1`` Miller loops into one accumulator and one final exponentiation.

Bytes are the live arguments and the answer at 48 bytes per Fp element:
affine keys, signatures and message points, 8-byte weights, one Fp12.
"""

from . import fp


def fp_mults(n: int, keys: int) -> int:
    aggregate = (keys - n) * fp.G1_ADD
    weights = n * fp.ladder(64, fp.G1_ADD, fp.G1_DBL)
    w_sum = 64 * fp.G2_DBL + 32 * n * fp.G2_ADD + fp.FP_INV + 3 * 3
    return aggregate + weights + w_sum + fp.multi_miller(n + 1) + fp.FINAL_EXP


def ops(n: int, keys: int) -> int:
    return fp_mults(n, keys) * fp.FP_MUL_INT8_OPS


def nbytes(n: int, keys: int) -> int:
    b = fp.FP_BYTES
    return keys * 2 * b + n * (4 * b + 4 * b + 8) + 12 * b


def roofline_pct(n: int, keys: int, seconds: float, peak: dict):
    return fp.roofline_pct(ops(n, keys), nbytes(n, keys), seconds, peak)
