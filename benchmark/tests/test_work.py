"""The work models against counts made by hand at small sizes."""

import pytest

from benchmark.work import bls_verify, fp, kzg_batch

PEAK = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_shared_counts_by_hand():
    assert fp.FP_MUL_INT8_OPS == 9312            # 2 * (2 * 48 * 48 + 48)
    assert (fp.G1_ADD, fp.G1_DBL, fp.G2_ADD, fp.G2_DBL) == (16, 7, 43, 16)
    assert fp.multi_miller(2) == 63 * 36 + 2 * (63 * 64 + 5 * 80) == 11132
    assert fp.FINAL_EXP == 799 + 7620


@pytest.mark.parametrize("n,keys,want", [
    # weights 960 + W sum (1024 + 1376 + 580) + Miller 11132 + final exp 8419
    (1, 1, 960 + 2980 + 11132 + 8419),
    # 3 aggregation adds 48 + 1920 + (1024 + 2752 + 580) + 15564 + 8419
    (2, 5, 48 + 1920 + 4356 + 15564 + 8419),
])
def test_bls_verify_by_hand(n, keys, want):
    assert bls_verify.fp_mults(n, keys) == want
    assert bls_verify.ops(n, keys) == want * 9312


@pytest.mark.parametrize("n,want", [
    # 3 ladders of 255 bits (1785 + 2032) + the generator's + 2 adds + 2 Miller + fe
    (1, 3 * 3817 + 3817 + 32 + 11132 + 8419),
    (2, 6 * 3817 + 3 * 16 + 3817 + 32 + 11132 + 8419),
])
def test_kzg_batch_by_hand(n, want):
    assert kzg_batch.fp_mults(n) == want


def test_bytes_by_hand():
    assert bls_verify.nbytes(1, 1) == 96 + (192 + 192 + 8) + 576
    assert kzg_batch.nbytes(1) == 2 * 96 + 3 * 32 + 192 + 576


def test_roofline_share_and_bound():
    ops = bls_verify.ops(131, 62978)
    share, bound = bls_verify.roofline_pct(131, 62978, 0.1, PEAK)
    assert bound == "int8_ops"
    assert share == pytest.approx(100 * ops / 393e12 / 0.1)
    # the least time the chip could take reads 100 %
    assert bls_verify.roofline_pct(131, 62978, ops / 393e12, PEAK)[0] == pytest.approx(100)
    assert fp.roofline_pct(1.0, 1e9, 1.0, PEAK)[1] == "hbm_bytes"
    assert fp.roofline_pct(1.0, 1.0, 0.0, PEAK) == (None, None)


def test_peaks_unknown_kind_is_an_error():
    from benchmark.peaks import UnknownDevice, peaks

    assert peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(UnknownDevice):
        peaks("cpu")
