"""Cofactor clearing on the device: ``ec.g2_psi``, ``ec.g2_clear_cofactor``
and the ``h2c_clear`` stage of ``ops/verify._device_verify`` against the
host golden model (``curve.psi``, ``curve.clear_cofactor_g2``), and the
verifier's verdicts now that the host hashes messages only to E2."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls import api, curve
from lighthouse_tpu.crypto.bls.backends import host
from lighthouse_tpu.crypto.bls.backends.host import _rand_scalars
from lighthouse_tpu.crypto.bls.fields import Fq2
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2, hash_to_g2_uncleared
from lighthouse_tpu.crypto.bls.params import DST, R
from lighthouse_tpu.ops import ec, pairing, tower
from lighthouse_tpu.ops import verify as verify_mod

LANES = 8
rng = random.Random(0x42C)


def _messages():
    """Q0 + Q1 of eight messages: what the marshal packs."""
    return [hash_to_g2_uncleared(b"h2c-%d" % i * 8, DST) for i in range(LANES)]


def _off_subgroup():
    """Points of E2 outside G2: x = k + u for the first k where x^3 + b'
    is a square."""
    out, k = [], 1
    while len(out) < LANES:
        x = Fq2(k, 1)
        y = (x * x * x + curve.B2_FQ2).sqrt()
        if y is not None:
            assert not curve.in_g2((x, y))
            out.append((x, y))
        k += 1
    return out


def _generator_and_g2():
    """The generator a pad lane holds, and points already in G2."""
    return [curve.G2] + [curve.mul(curve.G2, rng.randrange(1, R))
                         for _ in range(LANES - 1)]


POINTS = {"messages": _messages, "off_subgroup": _off_subgroup,
          "generator_and_g2": _generator_and_g2}


def _projective(points, scale):
    """(λx : λy : λ) of each affine point, λ = scale + (scale - 1)u (a scale
    of 1 keeps z = 1)."""
    lam = Fq2(scale, scale - 1)
    coords = [[c * lam for c in pt] + [lam] for pt in points]
    return tuple(jnp.asarray(np.stack([tower.fq2_to_limbs(p[i]) for p in coords]))
                 for i in range(3))


def _lane(coords, i):
    return ec.g2_from_limbs(tuple(np.asarray(c)[i] for c in coords))


_psi = jax.jit(ec.g2_psi)
_clear = jax.jit(ec.g2_clear_cofactor)
_stage = jax.jit(verify_mod._h2c_clear)


@pytest.mark.parametrize("kind", sorted(POINTS))
def test_g2_psi_matches_golden(kind):
    pts = POINTS[kind]()
    got = _psi(_projective(pts, 5))
    for i, pt in enumerate(pts):
        assert _lane(got, i) == curve.psi(pt)


@pytest.mark.parametrize("kind", sorted(POINTS))
def test_h2c_clear_stage_matches_golden(kind):
    """The stage's affine output, limb for limb after conversion, is
    ``curve.clear_cofactor_g2`` of each lane; every output lies in G2."""
    pts = POINTS[kind]()
    x, y, _ = _projective(pts, 1)
    hx, hy = _stage((x, y))
    one = np.asarray(tower.FQ2_ONE)
    for i, pt in enumerate(pts):
        got = ec.g2_from_limbs((np.asarray(hx)[i], np.asarray(hy)[i], one))
        want = curve.clear_cofactor_g2(pt)
        assert got == want and curve.in_g2(got)
    if kind == "messages":
        assert [curve.clear_cofactor_g2(pt) for pt in pts] == [
            hash_to_g2(b"h2c-%d" % i * 8, DST) for i in range(LANES)]


def test_clear_cofactor_of_projective_points_and_the_identity():
    """z other than 1 and the identity (0 : 1 : 0) in one batch."""
    pts = _messages()[:LANES - 1]
    x, y, z = _projective(pts, 7)
    ident = ec.identity(ec.G2_OPS, (1,))
    got = _clear(tuple(jnp.concatenate([c, i]) for c, i in zip((x, y, z), ident)))
    for i, pt in enumerate(pts):
        assert _lane(got, i) == curve.clear_cofactor_g2(pt)
    assert _lane(got, LANES - 1) is None


def _sets(n=4):
    out = []
    for i in range(n):
        sks = [api.SecretKey(4000 + 7 * i + j) for j in range(1 + i % 2)]
        msg = bytes([0x30 + i]) * 32
        sig = api.AggregateSignature.infinity()
        for sk in sks:
            sig.add_assign(sk.sign(msg))
        out.append(api.SignatureSet.multiple_pubkeys(
            sig, [sk.public_key() for sk in sks], msg))
    return out


def _tampered_signature():
    sets = _sets()
    s = sets[2]
    other = api.SecretKey(4014).sign(b"not the message" * 2)
    sets[2] = api.SignatureSet(other, s.message, s.signing_keys)
    return sets


def _swapped_message():
    sets = _sets()
    s = sets[1]
    sets[1] = api.SignatureSet(s.signature, sets[0].message, s.signing_keys)
    return sets


@pytest.mark.parametrize("make_sets, want", [
    (_sets, True), (_tampered_signature, False), (_swapped_message, False),
], ids=["good", "tampered_signature", "swapped_message"])
def test_device_verify_verdicts_with_the_clear_on_the_device(make_sets, want):
    sets = make_sets()
    rands = _rand_scalars(len(sets), b"h2c")
    batch = verify_mod.build_batch(sets, rands)
    assert verify_mod.bucket_shape(batch) == (4, 2)
    fe, w_z = verify_mod._device_verify(*batch)
    assert not tower.fq2_from_limbs(np.asarray(w_z)).is_zero()
    assert pairing.fe_is_one(fe) is want
    assert host.verify_signature_sets(sets, seed=b"h2c") is want
