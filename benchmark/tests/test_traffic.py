"""The traffic generator's items against the program's host golden model:
valid items are accepted, tampered ones refused, and the benchmark's own
arithmetic agrees with the program's where both compute the same thing."""

import pytest

from benchmark import ecref, kzgref
from benchmark.traffic import gen

from .conftest import ROOT, TINY

SEED = 2 ** 31 + 11


def test_ecref_matches_the_golden_model():
    from lighthouse_tpu.crypto.bls import curve, serde
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.crypto.bls.params import DST

    for msg in (b"", b"abc", bytes(range(32))):
        h = hash_to_g2(msg, DST)
        assert ecref.hash_to_g2(msg) == ((h[0].c0, h[0].c1), (h[1].c0, h[1].c1))
    keys = ecref.g1_chain(1000, 300, lanes=7)
    for i in (0, 1, 42, 299):
        want = curve.mul(curve.G1, 1000 + i)
        assert keys[i] == (want[0].n, want[1].n)
        assert ecref.g1_compress(keys[i]) == serde.g1_compress(want)
    sig = ecref.sign_with(b"m", 12345)
    want = curve.mul(hash_to_g2(b"m", DST), 12345)
    assert sig == ((want[0].c0, want[0].c1), (want[1].c0, want[1].c1))


def _traffic(kind, seed=SEED):
    traffic = gen.open_traffic(TINY, {"kind": kind, "tamper_every": 2}, seed, ROOT)
    traffic.take_shared([gen.run_job(traffic.path, fn, a)
                         for fn, a in traffic.shared_jobs()])
    return traffic


def _blocks(n):
    traffic = _traffic("block_sigs")
    return traffic, [gen.make_inline(traffic, "window", i) for i in range(n)]


def test_blocks_valid_and_tampered_by_the_host_model():
    from lighthouse_tpu.crypto.bls.backends import host

    traffic, items = _blocks(8)
    kinds = set()
    for raw in items:
        sets = traffic.request(raw)
        assert len(sets) == TINY["MAX_ATTESTATIONS"] + 3
        keys = [k for s in sets for k in s.signing_keys]
        assert len(set(map(id, keys))) == len(keys), "keys repeat in a block"
        assert host.verify_signature_sets(sets) is raw["expect"]
        if not raw["expect"]:
            kinds.add(raw["tampered_set"])
            for j, s in enumerate(sets):
                assert s.verify() is (j != raw["tampered_set"])
    # in 8 items at one in 2: attestation, sync, attestation, proposer
    n_att = TINY["MAX_ATTESTATIONS"]
    assert {n_att, n_att + 1} <= kinds
    assert any(k < n_att for k in kinds)
    msgs = [m for raw in items for m in raw["msgs"]]
    assert len(set(msgs)) == len(msgs), "a message repeats across blocks"


def test_tampered_sets_rotate():
    """Every other tampered block has an attestation wrong, at a seeded row;
    the others the sync aggregate, the proposer and RANDAO in turn."""
    import random

    kind = gen.load_module(gen.kind_path("block_sigs", ROOT))
    rng = random.Random(1)
    bad = [kind.bad_set(g, rng, 128) for g in range(12)]
    assert all(b < 128 for b in bad[0::2]) and len(set(bad[0::2])) > 3
    assert bad[1::2] == [128, 129, 130, 128, 129, 130]


def test_items_depend_on_seed_and_index_only():
    _, a = _blocks(2)
    b = gen.make_inline(_traffic("block_sigs"), "window", 1)
    assert a[1]["sigs"] == b["sigs"] and a[1]["msgs"] == b["msgs"]
    other = gen.make_inline(_traffic("block_sigs", SEED + 1), "window", 1)
    assert other["msgs"] != b["msgs"]


def test_blobs_valid_and_tampered_by_the_host_model():
    traffic = _traffic("blob_da")
    traffic.kzg.device = False  # the host golden model
    seen = set()
    for i in range(4):
        raw = gen.make_inline(traffic, "window", i)
        assert traffic.kzg.verify_blob_kzg_proof_batch(*traffic.request(raw)) \
            is raw["expect"]
        seen.add(raw["expect"])
    assert seen == {True, False}


def test_known_tau_commitments_match_the_program_prover():
    import numpy as np
    from lighthouse_tpu.crypto.kzg import Kzg, TrustedSetup

    width, tau = TINY["FIELD_ELEMENTS_PER_BLOB"], 987654321
    blob = kzgref.blob_bytes(np.random.default_rng(5), width)
    mine = kzgref.KnownTauSetup(tau, width)
    kzg = Kzg(TrustedSetup.insecure_dev_setup(width=width, secret=tau))
    commitment, proof = mine.commit_and_prove(blob)
    assert commitment == kzg.blob_to_kzg_commitment(blob)
    assert proof == kzg.compute_blob_kzg_proof(blob, commitment)
    assert kzgref.roots_brp(width) == tuple(kzg.roots_brp)


@pytest.mark.parametrize("mix", ["block_sigs", "blob_da"])
def test_every_mix_names_a_kind_and_its_statistics(mix):
    import os

    from benchmark import run

    spec = gen.load_mix(mix, ROOT)
    assert os.path.exists(gen.kind_path(spec["kind"], ROOT))
    assert set(spec["report"].values()) <= set(run.STATS)
