"""The host marshal of ``ops/verify.build_batch``: its phases (subgroup
checks, hashing to E2 with the cofactor left for the device, packing)
return the same arrays as packing set by set, a
set wider than the top key bucket is laid over key rows of that width, and
a bad signature anywhere stops the batch before any message is hashed."""

import collections

import numpy as np
import pytest

from lighthouse_tpu import tracing
from lighthouse_tpu.crypto.bls import api, curve
from lighthouse_tpu.crypto.bls.backends.host import _rand_scalars
from lighthouse_tpu.crypto.bls.fields import Fq
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2_uncleared
from lighthouse_tpu.crypto.bls.params import P
from lighthouse_tpu.ops import ec, tower
from lighthouse_tpu.ops import verify as verify_mod


def _signed_sets(shape):
    """One signed set per ``(n_keys, message)`` of ``shape``."""
    out = []
    for i, (n_keys, msg) in enumerate(shape):
        sks = [api.SecretKey(1000 + 17 * i + j) for j in range(n_keys)]
        sig = api.AggregateSignature.infinity()
        for sk in sks:
            sig.add_assign(sk.sign(msg))
        out.append(api.SignatureSet.multiple_pubkeys(
            sig, [sk.public_key() for sk in sks], msg))
    return out


def _mixed_sets():
    """Key lists of 1, 3 and 2 keys (the key axis pads to 4), two sets that
    share a message (a cache hit), and one multi-key aggregate."""
    return _signed_sets([(1, b"a" * 32), (3, b"b" * 32),
                         (2, b"a" * 32), (1, b"c" * 32)])


def _full_bucket_sets():
    """A set of 8 keys fills the 8-key bucket exactly, beside a 1-key set."""
    return _signed_sets([(8, b"a" * 32), (1, b"b" * 32)])


def _padding_row_sets():
    """Five sets bucket to eight rows: three rows of pure padding."""
    return _signed_sets([(2, b"a" * 32), (1, b"b" * 32), (1, b"a" * 32),
                         (3, b"c" * 32), (1, b"b" * 32)])


def _repeated_message_sets():
    """Six sets over three messages, one of them signed four times."""
    return _signed_sets([(1, b"a" * 32), (2, b"b" * 32), (1, b"a" * 32),
                         (3, b"a" * 32), (1, b"c" * 32), (2, b"a" * 32)])


def _edge_coordinate_sets():
    """Keys whose coordinates are 0, 1 and p - 1 (not curve points: the
    marshal packs what the key holds), under valid signatures."""
    edge = [(0, 1), (1, P - 1), (P - 1, 0), (P - 1, P - 1), (0, 0)]
    keys = [api.PublicKey((Fq(x), Fq(y)), _skip_checks=True) for x, y in edge]
    sets = _mixed_sets()[:3]
    return [api.SignatureSet(sets[0].signature, sets[0].message, keys[:1]),
            api.SignatureSet(sets[1].signature, sets[1].message, keys[1:4]),
            api.SignatureSet(sets[2].signature, sets[2].message,
                             [keys[4], sets[2].signing_keys[0]])]


def _reference_pack(sets, rands):
    """The set-by-set packing: each set checked, hashed and packed in turn;
    a set wider than the top key bucket takes as many key rows of that
    width as it fills, the next set starting on a fresh row."""
    n = len(sets)
    nb = verify_mod._bucket(n, verify_mod.N_BUCKETS)
    widest = max(len(s.signing_keys) for s in sets)
    top = verify_mod.K_BUCKETS[-1]
    kb = top if widest > top else verify_mod._bucket(widest, verify_mod.K_BUCKETS)
    set_rows = [max(1, -(-len(s.signing_keys) // kb)) for s in sets]
    nr = verify_mod._bucket(sum(set_rows), verify_mod.N_BUCKETS) if widest > top else nb
    rows = None
    if widest > top:
        rows = np.full((nb, verify_mod._bucket(max(set_rows), verify_mod.N_BUCKETS)),
                       -1, np.int32)
    pk = [np.zeros((nr, kb, 25), np.int32) for _ in range(3)]
    sig = [np.zeros((nb, 2, 25), np.int32) for _ in range(3)]
    msg = [np.zeros((nb, 2, 25), np.int32) for _ in range(2)]
    wbits = np.zeros((nb, 64), np.int32)
    live = np.zeros((nb,), bool)
    id1, id2 = ec.g1_to_limbs(None), ec.g2_to_limbs(None)
    for c in range(3):
        pk[c][:] = id1[c]
        sig[c][:] = id2[c]
    for c in range(2):
        msg[c][:] = verify_mod._G2_GEN_AFF[c]
    first = 0
    for i, (s, r) in enumerate(zip(sets, rands)):
        sig_pt = s.signature.point
        assert sig_pt is not None and curve.in_g2(sig_pt) and s.signing_keys
        sl = ec.g2_to_limbs(sig_pt)
        h = hash_to_g2_uncleared(bytes(s.message), verify_mod.DST)
        for c in range(3):
            sig[c][i] = sl[c]
        msg[0][i] = tower.fq2_to_limbs(h[0])
        msg[1][i] = tower.fq2_to_limbs(h[1])
        for j, key in enumerate(s.signing_keys):
            kl = ec.g1_to_limbs(key.point)
            for c in range(3):
                pk[c][first + j // kb, j % kb] = kl[c]
        if rows is not None:
            rows[i, :set_rows[i]] = range(first, first + set_rows[i])
        first += set_rows[i]
        wbits[i] = ec.bits_msb(r, 64)
        live[i] = True
    return tuple(pk), tuple(sig), tuple(msg), wbits, live, rows


def _leaves(batch):
    pk, sig, msg, wbits, live, rows = batch
    return [*pk, *sig, *msg, wbits, live] + ([] if rows is None else [rows])


@pytest.mark.parametrize("make_sets, shape", [
    (_mixed_sets, (4, 4)),
    (_full_bucket_sets, (2, 8)),
    (_padding_row_sets, (8, 4)),
    (_edge_coordinate_sets, (4, 4)),
    (_repeated_message_sets, (8, 4)),
], ids=["mixed", "full_key_bucket", "padding_rows", "edge_coordinates",
        "repeated_messages"])
def test_phased_marshal_matches_set_by_set_packing(monkeypatch, make_sets,
                                                   shape):
    monkeypatch.setattr(verify_mod, "_hash_cache", {})
    hashed = []
    real = verify_mod.hash_to_g2_uncleared_many
    monkeypatch.setattr(verify_mod, "hash_to_g2_uncleared_many",
                        lambda ms, dst: hashed.append(list(ms)) or real(ms, dst))
    sets = make_sets()
    rands = _rand_scalars(len(sets), b"marshal-seed")
    with tracing.span("unit_marshal") as root:
        got = verify_mod.build_batch(sets, rands)
    # each distinct message hashed once, in order of first use, in one call
    distinct = list(dict.fromkeys(bytes(s.message) for s in sets))
    assert hashed == [distinct]
    want = _reference_pack(sets, rands)
    assert got[4].shape == (shape[0],) and got[0][0].shape[:2] == shape
    assert got[5] is None and want[5] is None  # one key row a set
    assert len(_leaves(got)) == len(_leaves(want))
    for g, w in zip(_leaves(got), _leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    names = [c.name for c in root.children]
    assert names == ["marshal_subgroup", "marshal_hash_to_g2", "marshal_pack"]
    span = root.children[1]
    assert span.fields["n_messages"] == len(sets)
    assert span.fields["cleared_on_device"] == len(sets)
    assert span.fields["n_hash_misses"] == len(distinct)
    assert span.fields["modexp"] == 4 * len(distinct) + 3
    assert root.children[2].fields["n_keys"] == sum(
        len(s.signing_keys) for s in sets)
    assert root.children[2].fields["n_rows"] == len(sets)
    assert root.children[2].fields["k_row"] == shape[1]


@pytest.mark.parametrize("widths, bucket, rows", [
    # 9 keys over rows of 4: rows 0-2; the 1-key set row 3
    ((9, 1), (2, 4, 4, 4), [[0, 1, 2, -1], [3, -1, -1, -1]]),
    # 1 / 3 / 9: one row each for the first two, three for the last
    ((1, 3, 9), (4, 4, 8, 4), [[0, -1, -1, -1], [1, -1, -1, -1],
                               [2, 3, 4, -1], [-1, -1, -1, -1]]),
    # 8 keys fill two rows exactly; 12 keys three
    ((8, 12), (2, 4, 8, 4), [[0, 1, -1, -1], [2, 3, 4, -1]]),
], ids=["wide_and_one", "mixed_1_3_9", "exact_rows"])
def test_wide_sets_span_key_rows(monkeypatch, widths, bucket, rows):
    """Past the top key bucket (shrunk to 4 here) every key row is 4 wide,
    a set takes ceil(w / 4) consecutive rows, and ``rows`` lists them; the
    bytes match the set-by-set packing."""
    monkeypatch.setattr(verify_mod, "K_BUCKETS", (1, 2, 4))
    sets = _signed_sets([(w, bytes([97 + i]) * 32) for i, w in enumerate(widths)])
    rands = _rand_scalars(len(sets), b"rows-seed")
    with tracing.span("unit_marshal") as root:
        got = verify_mod.build_batch(sets, rands)
    want = _reference_pack(sets, rands)
    assert verify_mod.bucket_shape(got) == bucket
    assert got[5].tolist() == rows
    for g, w in zip(_leaves(got), _leaves(want), strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    pack = root.children[2]
    assert pack.fields["n_rows"] == sum(-(-w // 4) for w in widths)
    assert pack.fields["k_row"] == 4


def test_hash_misses_are_counted_where_the_cache_is_consulted(monkeypatch):
    monkeypatch.setattr(verify_mod, "_hash_cache", {})
    sets = _mixed_sets()
    with tracing.span("unit_marshal") as root:
        verify_mod.build_batch(sets, _rand_scalars(len(sets), b"s"))
    # three distinct messages among four sets; the device clears all four
    assert root.children[1].fields["n_hash_misses"] == 3
    assert root.children[1].fields["modexp"] == 4 * 3 + 3
    assert root.children[1].fields["cleared_on_device"] == 4
    with tracing.span("unit_marshal_again") as again:
        verify_mod.build_batch(sets, _rand_scalars(len(sets), b"s"))
    assert again.children[1].fields["n_hash_misses"] == 0
    assert again.children[1].fields["modexp"] == 0  # every message a hit
    assert again.children[1].fields["cleared_on_device"] == 4


@pytest.mark.parametrize("bad", ["missing", "no_keys"])
def test_bad_last_set_returns_none_before_any_hashing(monkeypatch, bad):
    monkeypatch.setattr(verify_mod, "_hash_cache", {})
    hashed = []
    real = verify_mod.hash_to_g2_uncleared_many
    monkeypatch.setattr(verify_mod, "hash_to_g2_uncleared_many",
                        lambda ms, dst: hashed.append(ms) or real(ms, dst))
    sets = _mixed_sets()
    last = sets[-1]
    if bad == "missing":
        sets[-1] = api.SignatureSet(api.Signature.empty(), last.message,
                                    last.signing_keys)
    else:
        sets[-1] = api.SignatureSet(last.signature, last.message, [])
    assert verify_mod.build_batch(sets, _rand_scalars(len(sets), b"s")) is None
    assert hashed == []


@pytest.mark.parametrize("first, then, k_buckets", [
    (_mixed_sets, _edge_coordinate_sets, None),
    (lambda: _signed_sets([(1, b"a" * 32), (3, b"b" * 32), (9, b"c" * 32)]),
     lambda: _signed_sets([(8, b"d" * 32), (12, b"e" * 32)]), (1, 2, 4)),
], ids=["one_row_a_set", "key_rows"])
def test_recycled_key_arrays_pack_the_same_bytes(monkeypatch, first, then,
                                                 k_buckets):
    """A finished batch's key arrays, handed back, are taken by the next
    batch of their shape and hold what new arrays would: no key of the
    earlier batch is left in a row the later one pads."""
    monkeypatch.setattr(verify_mod, "_KEY_ARRAYS", collections.OrderedDict())
    if k_buckets:
        monkeypatch.setattr(verify_mod, "K_BUCKETS", k_buckets)
    sets = first()
    earlier = verify_mod.build_batch(sets, _rand_scalars(len(sets), b"one"))
    verify_mod._return_key_arrays(earlier[0])
    sets = then()
    rands = _rand_scalars(len(sets), b"two")
    got = verify_mod.build_batch(sets, rands)
    assert all(g is e for g, e in zip(got[0], earlier[0], strict=True))
    for g, w in zip(_leaves(got), _leaves(_reference_pack(sets, rands)),
                    strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def test_key_arrays_kept_one_set_a_shape_within_their_bytes(monkeypatch):
    """One set a shape, the newest kept; past the byte cap the least
    recently returned go first; a set larger than the cap is not kept."""
    def arrays(nr, kb):
        return [np.zeros((nr, kb, 25), np.int32) for _ in range(3)]

    monkeypatch.setattr(verify_mod, "_KEY_ARRAYS", collections.OrderedDict())
    monkeypatch.setattr(verify_mod, "_KEY_ARRAYS_MAX_BYTES", 2 * 3 * 4 * 4 * 100)
    a, b, c = arrays(4, 4), arrays(2, 8), arrays(4, 4)
    for pk in (a, b, c):
        verify_mod._return_key_arrays(pk)
    assert list(verify_mod._KEY_ARRAYS) == [(2, 8), (4, 4)]
    assert verify_mod._KEY_ARRAYS[(4, 4)][0] is c[0]
    used = arrays(1, 32)
    for arr in used:
        arr[:] = 7
    verify_mod._return_key_arrays(used)  # as large as both
    assert list(verify_mod._KEY_ARRAYS) == [(1, 32)]
    verify_mod._return_key_arrays(arrays(8, 8))  # larger than the cap
    assert list(verify_mod._KEY_ARRAYS) == [(1, 32)]
    taken = verify_mod._key_arrays(1, 32)
    assert [t is u for t, u in zip(taken, used)] == [True] * 3
    assert not verify_mod._KEY_ARRAYS and not any(t.any() for t in taken)
    fresh = verify_mod._key_arrays(4, 4)
    assert [a.shape for a in fresh] == [(4, 4, 25)] * 3
