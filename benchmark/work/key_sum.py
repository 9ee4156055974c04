"""Work of summing each signature set's public keys into one aggregate key
(``_device_verify``'s stages ``s1_key_sum`` and ``s1_set_sum``).

Counted from the LIVE request, not the program's key rows: ``n`` sets
holding ``keys`` public keys in all take ``keys - n`` G1 additions however
the keys are laid over rows, and read each key's affine coordinates once.
"""

from . import fp


def ops(n: int, keys: int) -> int:
    return (keys - n) * fp.G1_ADD * fp.FP_MUL_INT8_OPS


def nbytes(n: int, keys: int) -> int:
    return keys * 2 * fp.FP_BYTES


def roofline_pct(n: int, keys: int, seconds: float, peak: dict):
    return fp.roofline_pct(ops(n, keys), nbytes(n, keys), seconds, peak)
