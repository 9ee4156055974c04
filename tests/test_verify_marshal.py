"""The host marshal of ``ops/verify.build_batch``: its phases (subgroup
checks, hashing, packing) return the same arrays as packing set by set, and
a bad signature anywhere stops the batch before any message is hashed."""

import numpy as np
import pytest

from lighthouse_tpu import tracing
from lighthouse_tpu.crypto.bls import api, curve
from lighthouse_tpu.crypto.bls.backends.host import _rand_scalars
from lighthouse_tpu.ops import ec, tower
from lighthouse_tpu.ops import verify as verify_mod


def _mixed_sets():
    """Key lists of 1, 3 and 2 keys (the key axis pads to 4), two sets that
    share a message (a cache hit), and one multi-key aggregate."""
    out = []
    for i, (n_keys, msg) in enumerate([(1, b"a" * 32), (3, b"b" * 32),
                                       (2, b"a" * 32), (1, b"c" * 32)]):
        sks = [api.SecretKey(1000 + 17 * i + j) for j in range(n_keys)]
        sig = api.AggregateSignature.infinity()
        for sk in sks:
            sig.add_assign(sk.sign(msg))
        out.append(api.SignatureSet.multiple_pubkeys(
            sig, [sk.public_key() for sk in sks], msg))
    return out


def _reference_pack(sets, rands):
    """The set-by-set packing: each set checked, hashed and packed in turn."""
    n = len(sets)
    nb = verify_mod._bucket(n, verify_mod.N_BUCKETS)
    kb = verify_mod._bucket(max(len(s.signing_keys) for s in sets),
                            verify_mod.K_BUCKETS)
    pk = [np.zeros((nb, kb, 25), np.int32) for _ in range(3)]
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
    for i, (s, r) in enumerate(zip(sets, rands)):
        sig_pt = s.signature.point
        assert sig_pt is not None and curve.in_g2(sig_pt) and s.signing_keys
        sl = ec.g2_to_limbs(sig_pt)
        h = verify_mod.hash_to_g2(bytes(s.message), verify_mod.DST)
        for c in range(3):
            sig[c][i] = sl[c]
        msg[0][i] = tower.fq2_to_limbs(h[0])
        msg[1][i] = tower.fq2_to_limbs(h[1])
        for j, key in enumerate(s.signing_keys):
            kl = ec.g1_to_limbs(key.point)
            for c in range(3):
                pk[c][i, j] = kl[c]
        wbits[i] = ec.bits_msb(r, 64)
        live[i] = True
    return tuple(pk), tuple(sig), tuple(msg), wbits, live


def _leaves(batch):
    pk, sig, msg, wbits, live = batch
    return [*pk, *sig, *msg, wbits, live]


def test_phased_marshal_matches_set_by_set_packing():
    sets = _mixed_sets()
    rands = _rand_scalars(len(sets), b"marshal-seed")
    with tracing.span("unit_marshal") as root:
        got = verify_mod.build_batch(sets, rands)
    want = _reference_pack(sets, rands)
    for g, w in zip(_leaves(got), _leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    names = [c.name for c in root.children]
    assert names == ["marshal_subgroup", "marshal_hash_to_g2", "marshal_pack"]
    hashed = root.children[1]
    assert hashed.fields["n_messages"] == 4
    assert root.children[2].fields["n_keys"] == 7


def test_hash_misses_are_counted_where_the_cache_is_consulted(monkeypatch):
    monkeypatch.setattr(verify_mod, "_hash_cache", {})
    sets = _mixed_sets()
    with tracing.span("unit_marshal") as root:
        verify_mod.build_batch(sets, _rand_scalars(len(sets), b"s"))
    # three distinct messages among four sets
    assert root.children[1].fields["n_hash_misses"] == 3
    with tracing.span("unit_marshal_again") as again:
        verify_mod.build_batch(sets, _rand_scalars(len(sets), b"s"))
    assert again.children[1].fields["n_hash_misses"] == 0


@pytest.mark.parametrize("bad", ["missing", "no_keys"])
def test_bad_last_set_returns_none_before_any_hashing(monkeypatch, bad):
    monkeypatch.setattr(verify_mod, "_hash_cache", {})
    hashed = []
    real = verify_mod.hash_to_g2
    monkeypatch.setattr(verify_mod, "hash_to_g2",
                        lambda m, dst: hashed.append(m) or real(m, dst))
    sets = _mixed_sets()
    last = sets[-1]
    if bad == "missing":
        sets[-1] = api.SignatureSet(api.Signature.empty(), last.message,
                                    last.signing_keys)
    else:
        sets[-1] = api.SignatureSet(last.signature, last.message, [])
    assert verify_mod.build_batch(sets, _rand_scalars(len(sets), b"s")) is None
    assert hashed == []
