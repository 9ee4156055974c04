"""Shared counts, in 381-bit field multiplications (``m``; a squaring counts
as one), of the textbook operations both verifiers are built from, and the
one fixed cost that turns them into int8 operations.

Cost of one ``m`` on an int8 multiply-accumulate array: a 381-bit operand is
48 bytes.  The schoolbook product takes 48 * 48 byte MACs, and a Montgomery
reduction another 48 * 48 + 48, so one ``m`` is ``2 * 48**2 + 48`` MACs, or
twice that in int8 operations (a MAC is two operations, the convention of
the 393 TOP/s peak).  Derived once, here; whatever digit size a program
uses, the share is measured against this one count.
"""

FP_MUL_INT8_OPS = 2 * (2 * 48 * 48 + 48)
FP_BYTES = 48

# Jacobian coordinates, a = 0 (EFD add-2007-bl: 11M + 5S; dbl-2009-l: 2M + 5S)
G1_ADD, G1_DBL = 16, 7
# over Fp2: a multiplication is 3 m (Karatsuba), a squaring 2 m
G2_ADD, G2_DBL = 11 * 3 + 5 * 2, 2 * 3 + 5 * 2

# optimal ate on BLS12-381: |x| has 64 bits, 6 of them set
MILLER_DOUBLINGS, MILLER_ADDITIONS = 63, 5
FP12_SQR = 12 * 3            # 12 Fp2 multiplications
FP12_MUL = 18 * 3
LINE_MUL = 13 * 3            # sparse product with a line
LINE_DBL = 3 * 3 + 6 * 2 + 4  # G2 doubling + line coefficients (projective)
LINE_ADD = 11 * 3 + 2 * 2 + 4
FP_INV = 381 + 190           # by Fermat: 381 squarings and ~190 products
FINAL_EXP_EASY = (FP_INV + 100) + 2 * FP12_MUL + 20
FINAL_EXP_HARD = 5 * (63 * 18 + 5 * FP12_MUL) + 10 * FP12_MUL + 3 * 20
FINAL_EXP = FINAL_EXP_EASY + FINAL_EXP_HARD


def ladder(bits: int, group_add: int, group_dbl: int) -> int:
    """Double-and-add over a ``bits``-bit scalar, half its bits set."""
    return bits * group_dbl + (bits // 2) * group_add


def multi_miller(pairs: int) -> int:
    """One accumulator for ``pairs`` Miller loops: its squarings are shared,
    each pair adds its own line steps."""
    per_pair = (MILLER_DOUBLINGS * (LINE_DBL + LINE_MUL)
                + MILLER_ADDITIONS * (LINE_ADD + LINE_MUL))
    return MILLER_DOUBLINGS * FP12_SQR + pairs * per_pair


def roofline_pct(ops: float, nbytes: float, seconds: float, peak: dict):
    """``(share %, bound)``: the least time the chip could take over the
    time measured.  None when nothing was measured."""
    if not seconds or seconds <= 0:
        return None, None
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "int8_ops" if t_ops >= t_bytes else "hbm_bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
