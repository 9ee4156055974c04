"""Work of one batch check of ``n`` blobs' KZG proofs (``kzg_batch``).

The textbook batch equation (Deneb ``verify_kzg_proof_batch``, with the
``y`` terms folded into one scalar) needs ``sum r_i P_i`` and
``sum r_i (C_i + z_i P_i) - [sum r_i y_i] G1``: ``3n`` G1 scalar
multiplications with 255-bit scalars (an n-point and a 2n-point MSM, each
by double-and-add), their ``3(n - 1)`` additions, one 255-bit generator
multiplication and two additions, then 2 Miller loops and one final
exponentiation.  ISSUE 22 counted only the 2n-point MSM; the n-point one
over the proofs is needed as well.

Bytes: the live arguments (``2n`` affine G1 points, ``3n`` 32-byte
scalars, the affine G2 point ``[tau]``) and one Fp12.
"""

from . import fp


def fp_mults(n: int) -> int:
    mul255 = fp.ladder(255, fp.G1_ADD, fp.G1_DBL)
    msm = 3 * n * mul255 + 3 * (n - 1) * fp.G1_ADD
    return msm + mul255 + 2 * fp.G1_ADD + fp.multi_miller(2) + fp.FINAL_EXP


def ops(n: int) -> int:
    return fp_mults(n) * fp.FP_MUL_INT8_OPS


def nbytes(n: int) -> int:
    b = fp.FP_BYTES
    return 2 * n * 2 * b + 3 * n * 32 + 4 * b + 12 * b


def roofline_pct(n: int, seconds: float, peak: dict):
    return fp.roofline_pct(ops(n), nbytes(n), seconds, peak)
