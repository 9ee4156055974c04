"""The 4096-set standard bucket in the production dispatch path.

Fast structural coverage for tier-1 (bucket selection, oversized-batch
chunking, scheduler coalescing aligned with the top bucket, padded uneven
verdict parity) plus the full-size 4096-bucket execution as an opt-in slow
test — on this 1-core CPU host the real 4096x32 program takes ~40 min/rep
(PERF.md big-bucket table), which no routine suite should pay.
"""

import os

import numpy as np
import pytest

from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.crypto.bls.backends import host
from lighthouse_tpu.ops import verify as v


def make_set(msg: bytes, n_keys: int = 1):
    sks = [api.SecretKey.random() for _ in range(n_keys)]
    agg = api.AggregateSignature.infinity()
    for sk in sks:
        agg.add_assign(sk.sign(msg))
    return api.SignatureSet.multiple_pubkeys(
        agg, [sk.public_key() for sk in sks], msg)


def test_bucket_selection_promotes_4096_top_bucket():
    assert v.N_BUCKETS[-1] == 4096
    assert v.MAX_SETS_PER_DISPATCH == 4096
    assert v._bucket(2049, v.N_BUCKETS) == 4096
    assert v._bucket(4096, v.N_BUCKETS) == 4096
    with pytest.raises(ValueError):
        v._bucket(4097, v.N_BUCKETS)


def test_scheduler_coalescing_matches_standard_bucket():
    """One drained scheduler batch feeds one device program: the gossip
    coalescing cap must equal the production top bucket, or the big buckets
    never fill under real traffic."""
    from lighthouse_tpu.scheduler import work

    assert work.STANDARD_DEVICE_BATCH == v.N_BUCKETS[-1]
    for _, max_batch in work.BATCH_RULES.values():
        assert max_batch == work.STANDARD_DEVICE_BATCH


def test_oversized_batch_chunks_through_top_bucket(monkeypatch):
    """Batches beyond the top bucket chunk through MAX_SETS_PER_DISPATCH-
    set dispatches (verdicts AND) instead of raising — exercised with a
    shrunk cap so the test stays at small compiled shapes."""
    monkeypatch.setattr(v, "MAX_SETS_PER_DISPATCH", 2)
    sets = [make_set(b"chunk-%d" % i) for i in range(5)]
    assert v.verify_signature_sets_device(sets, seed=b"t") is True

    sk = api.SecretKey.random()
    bad = api.SignatureSet.single_pubkey(
        sk.sign(b"other"), sk.public_key(), b"chunk-bad")
    # the bad set lands in the LAST chunk: every chunk still gets a verdict
    assert v.verify_signature_sets_device(sets + [bad], seed=b"t") is False


def test_padded_uneven_batch_matches_host_golden():
    """Uneven live count inside a bucket (3 live sets padded to the 4
    bucket, mixed key counts) — device verdict is bit-identical to the host
    golden model, for both the passing and failing batch."""
    sets = [make_set(b"pad-a"), make_set(b"pad-b", n_keys=2), make_set(b"pad-c")]
    assert v.verify_signature_sets_device(sets, seed=b"s") is True
    assert host.verify_signature_sets(sets, seed=b"s") is True

    sk = api.SecretKey.random()
    bad = api.SignatureSet.single_pubkey(
        sk.sign(b"x"), sk.public_key(), b"pad-bad")
    batch = sets[:2] + [bad]
    assert (v.verify_signature_sets_device(batch, seed=b"s")
            == host.verify_signature_sets(batch, seed=b"s") is False)


@pytest.mark.slow
@pytest.mark.skipif(
    os.environ.get("LIGHTHOUSE_TPU_RUN_HUGE_BUCKETS") != "1",
    reason="~40 min/rep on a 1-core CPU host; set "
           "LIGHTHOUSE_TPU_RUN_HUGE_BUCKETS=1 (or run on a TPU) to execute",
)
def test_4096_bucket_full_dispatch_matches_host():
    """The real thing: 3000 live sets (128 distinct, tiled — the device
    dataflow is value-independent) pad into the 4096 bucket and dispatch
    through the production supervised path; the verdict matches the host
    golden model bit-for-bit."""
    distinct = [make_set(b"scale-%d" % i) for i in range(128)]
    reps = -(-3000 // len(distinct))
    sets = (distinct * reps)[:3000]
    got = v.verify_signature_sets_device(sets, seed=b"scale")
    want = host.verify_signature_sets(sets, seed=b"scale")
    assert got is True and want is True


# ------------------------------------------------- sets wider than the top


@pytest.fixture
def row_of_four(monkeypatch):
    """The top key bucket shrunk to 4 keys, so a 9-key set spans three key
    rows, summed on the device by ``s1_set_sum``."""
    monkeypatch.setattr(v, "K_BUCKETS", (1, 2, 4))


def _signed(msg: bytes, n_keys: int, first_sk: int):
    """A set of ``n_keys`` fixed secret keys, signed by their sum."""
    sks = [api.SecretKey(first_sk + i) for i in range(n_keys)]
    agg = api.SecretKey(sum(sk.scalar for sk in sks))
    return api.SignatureSet.multiple_pubkeys(
        agg.sign(msg), [sk.public_key() for sk in sks], msg)


def _wide_batch():
    """A 9-key set (rows of 4: keys 0-3, 4-7, 8) beside a 1-key set."""
    return [_signed(b"wide-nine", 9, 1000), _signed(b"wide-one", 1, 2000)]


@pytest.mark.parametrize("tamper", [None, "wrong_signature"])
def test_set_wider_than_top_bucket_matches_host(row_of_four, tamper):
    from lighthouse_tpu import device_telemetry

    sets = _wide_batch()
    if tamper:
        sets[0] = api.SignatureSet(sets[1].signature, sets[0].message,
                                   sets[0].signing_keys)
    want = host.verify_signature_sets(sets, seed=b"rows")
    assert want is (tamper is None)
    assert v.verify_signature_sets_device(sets, seed=b"rows") is want
    rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")[0]
    assert rec["shape"] == "2x4x4x4" and not rec["host_fallback"]
    assert (rec["key_rows"], rec["key_slots"], rec["live_keys"]) == (4, 16, 10)


def test_mixed_widths_one_three_nine_match_host(row_of_four):
    sets = [_signed(b"mix-1", 1, 3000), _signed(b"mix-3", 3, 3100),
            _signed(b"mix-9", 9, 3200)]
    assert host.verify_signature_sets(sets, seed=b"mix") is True
    assert v.verify_signature_sets_device(sets, seed=b"mix") is True
    bad = sets[:2] + [_signed(b"mix-9", 9, 3201)]
    bad[2] = api.SignatureSet(bad[2].signature, b"mix-9-other", bad[2].signing_keys)
    assert v.verify_signature_sets_device(bad, seed=b"mix") is False


def test_key_swapped_in_a_middle_row_is_refused(row_of_four):
    """Key 5 of the 9-key set lies in its second row; a stranger's key there
    must make the batch False, as on the host."""
    sets = _wide_batch()
    keys = list(sets[0].signing_keys)
    keys[5] = api.SecretKey(4242).public_key()
    sets[0] = api.SignatureSet(sets[0].signature, sets[0].message, keys)
    assert host.verify_signature_sets(sets, seed=b"rows") is False
    assert v.verify_signature_sets_device(sets, seed=b"rows") is False


def test_split_retry_halves_of_wide_sets(row_of_four):
    """A transient dispatch error retries the batch as two halves, each of
    one wide set at its own row bucket; a bad half still decides False."""
    from lighthouse_tpu import fault_injection as fi
    from lighthouse_tpu import metrics

    good = [_signed(b"split-nine-a", 9, 5000), _signed(b"split-nine-b", 9, 5100)]
    bad = [good[0], api.SignatureSet(good[0].signature, good[1].message,
                                     good[1].signing_keys)]
    try:
        for sets, want in ((good, True), (bad, False)):
            before = metrics.DEVICE_HOST_FALLBACK.get(reason="device_error")
            splits = metrics.DEVICE_SPLIT_RETRIES.get(op="bls_verify",
                                                      outcome="success")
            fi.install("device.dispatch", "error", op="bls_verify", first_n=1)
            assert host.verify_signature_sets(sets, seed=b"split") is want
            assert v.verify_signature_sets_device(sets, seed=b"split") is want
            assert metrics.DEVICE_SPLIT_RETRIES.get(
                op="bls_verify", outcome="success") == splits + 1
            assert metrics.DEVICE_HOST_FALLBACK.get(reason="device_error") == before
    finally:
        fi.clear("device.dispatch")


def test_executed_batch_returns_its_key_arrays(row_of_four, monkeypatch):
    """Once the device has answered, a batch's host key arrays go back for
    the next batch of their shape, which packs into them and still gets the
    host's verdict; a batch whose device leg failed keeps its own."""
    import collections

    from lighthouse_tpu import fault_injection as fi

    monkeypatch.setattr(v, "_KEY_ARRAYS", collections.OrderedDict())
    built = v.build_device_batch(_wide_batch(), seed=b"rows")
    pk = built.host[0]
    assert v.execute_built_batch(built) is True
    assert built.host is None and v._KEY_ARRAYS[(4, 4)][0] is pk[0]

    sets = _wide_batch()
    sets[0] = api.SignatureSet(sets[1].signature, sets[0].message,
                               sets[0].signing_keys)
    again = v.build_device_batch(sets, seed=b"rows")
    assert again.host[0][0] is pk[0] and not v._KEY_ARRAYS
    assert v.execute_built_batch(again) is host.verify_signature_sets(
        sets, seed=b"rows") is False

    v._KEY_ARRAYS.clear()
    pair = [_signed(b"split-nine-a", 9, 5000), _signed(b"split-nine-b", 9, 5100)]
    failed = v.build_device_batch(pair, seed=b"split")
    try:
        fi.install("device.dispatch", "error", op="bls_verify", first_n=1)
        assert v.execute_built_batch(failed) is True  # by its halves
    finally:
        fi.clear("device.dispatch")
    assert failed.host is not None and (8, 4) not in v._KEY_ARRAYS
