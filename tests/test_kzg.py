"""KZG engine tests (modeled on the reference's EF KZG vector handlers,
``testing/ef_tests/src/cases/kzg_*.rs``, run here against a dev trusted setup
with a known secret so every claim is checkable in the scalar field)."""

import hashlib

import pytest

from lighthouse_tpu.crypto.bls import curve
from lighthouse_tpu.crypto.kzg import (
    BLS_MODULUS,
    Kzg,
    KzgError,
    TrustedSetup,
    blob_to_polynomial,
    bls_field_to_bytes,
    roots_of_unity_brp,
)
from lighthouse_tpu.crypto.kzg import g1 as g1mod
from lighthouse_tpu.crypto.kzg.kzg import G1_GEN

WIDTH = 64  # small domain: same code paths, seconds not minutes
TAU = 0x5EC2E7


@pytest.fixture(scope="module")
def kzg():
    return Kzg(TrustedSetup.insecure_dev_setup(width=WIDTH, secret=TAU))


def make_blob(seed: int, width: int = WIDTH) -> bytes:
    out = b""
    for i in range(width):
        x = int.from_bytes(hashlib.sha256(f"{seed}:{i}".encode()).digest(), "big")
        out += (x % BLS_MODULUS).to_bytes(32, "big")
    return out


class TestSetup:
    def test_lagrange_points_on_curve(self, kzg):
        assert all(g1mod.is_on_curve(p) for p in kzg.setup.g1_lagrange)

    def test_commitment_equals_f_tau(self, kzg):
        """With known tau, C must equal [f(tau)]G1 — validates the setup
        derivation, blob parsing, and the Pippenger MSM in one shot."""
        blob = make_blob(1)
        poly = blob_to_polynomial(blob, WIDTH)
        f_tau = kzg.evaluate_polynomial_in_evaluation_form(poly, TAU)
        expected = g1mod.scalar_mul(G1_GEN, f_tau)
        commitment = kzg.blob_to_kzg_commitment(blob)
        from lighthouse_tpu.crypto.kzg.kzg import _bytes_to_g1

        assert _bytes_to_g1(commitment) == expected

    def test_g2_tau(self, kzg):
        assert kzg.setup.g2_monomial[1] == curve.mul(curve.G2, TAU)


class TestRoots:
    def test_roots_are_nth_roots(self):
        for w in roots_of_unity_brp(WIDTH):
            assert pow(w, WIDTH, BLS_MODULUS) == 1
        assert len(set(roots_of_unity_brp(WIDTH))) == WIDTH

    def test_brp_involution(self):
        from lighthouse_tpu.crypto.kzg import bit_reversal_permutation

        seq = list(range(WIDTH))
        assert bit_reversal_permutation(bit_reversal_permutation(seq)) == seq


class TestEvaluate:
    def test_constant_poly(self, kzg):
        c = 0xDEADBEEF
        poly = [c] * WIDTH
        assert kzg.evaluate_polynomial_in_evaluation_form(poly, 12345) == c

    def test_in_domain_returns_entry(self, kzg):
        blob = make_blob(2)
        poly = blob_to_polynomial(blob, WIDTH)
        z = kzg.roots_brp[7]
        assert kzg.evaluate_polynomial_in_evaluation_form(poly, z) == poly[7]

    def test_linear_poly(self, kzg):
        # f(x) = 3x + 5 in evaluation form over the BRP domain.
        poly = [(3 * w + 5) % BLS_MODULUS for w in kzg.roots_brp]
        z = 987654321
        assert kzg.evaluate_polynomial_in_evaluation_form(poly, z) == (3 * z + 5) % BLS_MODULUS


class TestProveVerify:
    def test_blob_roundtrip(self, kzg):
        blob = make_blob(3)
        commitment = kzg.blob_to_kzg_commitment(blob)
        proof = kzg.compute_blob_kzg_proof(blob, commitment)
        assert kzg.verify_blob_kzg_proof(blob, commitment, proof)

    def test_tampered_blob_rejected(self, kzg):
        blob = make_blob(4)
        commitment = kzg.blob_to_kzg_commitment(blob)
        proof = kzg.compute_blob_kzg_proof(blob, commitment)
        bad = b"\x00" * 31 + b"\x01" + blob[32:]
        assert not kzg.verify_blob_kzg_proof(bad, commitment, proof)

    def test_wrong_proof_rejected(self, kzg):
        b1, b2 = make_blob(5), make_blob(6)
        c1 = kzg.blob_to_kzg_commitment(b1)
        p2 = kzg.compute_blob_kzg_proof(b2, kzg.blob_to_kzg_commitment(b2))
        assert not kzg.verify_blob_kzg_proof(b1, c1, p2)

    def test_point_eval_out_of_domain(self, kzg):
        blob = make_blob(7)
        commitment = kzg.blob_to_kzg_commitment(blob)
        z = bls_field_to_bytes(777777)
        proof, y = kzg.compute_kzg_proof(blob, z)
        assert kzg.verify_kzg_proof(commitment, z, y, proof)
        y_bad = bls_field_to_bytes((int.from_bytes(y, "big") + 1) % BLS_MODULUS)
        assert not kzg.verify_kzg_proof(commitment, z, y_bad, proof)

    def test_point_eval_in_domain(self, kzg):
        blob = make_blob(8)
        poly = blob_to_polynomial(blob, WIDTH)
        commitment = kzg.blob_to_kzg_commitment(blob)
        z = bls_field_to_bytes(kzg.roots_brp[13])
        proof, y = kzg.compute_kzg_proof(blob, z)
        assert int.from_bytes(y, "big") == poly[13]
        assert kzg.verify_kzg_proof(commitment, z, y, proof)


class TestBatch:
    def test_batch_roundtrip(self, kzg):
        blobs = [make_blob(10 + i) for i in range(4)]
        commitments = [kzg.blob_to_kzg_commitment(b) for b in blobs]
        proofs = [kzg.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, commitments)]
        assert kzg.verify_blob_kzg_proof_batch(blobs, commitments, proofs)

    def test_batch_one_bad_fails(self, kzg):
        blobs = [make_blob(20 + i) for i in range(3)]
        commitments = [kzg.blob_to_kzg_commitment(b) for b in blobs]
        proofs = [kzg.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, commitments)]
        proofs[1], proofs[2] = proofs[2], proofs[1]
        assert not kzg.verify_blob_kzg_proof_batch(blobs, commitments, proofs)

    def test_empty_batch_ok(self, kzg):
        assert kzg.verify_blob_kzg_proof_batch([], [], [])


class TestValidation:
    def test_noncanonical_blob_rejected(self, kzg):
        blob = (BLS_MODULUS).to_bytes(32, "big") + make_blob(30)[32:]
        with pytest.raises(KzgError):
            kzg.blob_to_kzg_commitment(blob)

    def test_bad_length_rejected(self, kzg):
        with pytest.raises(KzgError):
            kzg.blob_to_kzg_commitment(b"\x00" * 31)

    def test_not_on_curve_commitment_rejected(self, kzg):
        blob = make_blob(31)
        proof = b"\xc0" + b"\x00" * 47  # infinity — fine
        bad_commitment = b"\x80" + b"\x11" * 47  # compression flag unset
        with pytest.raises(KzgError):
            kzg.verify_blob_kzg_proof(blob, bad_commitment, proof)

    def test_bad_field_element_length_rejected(self, kzg):
        blob = make_blob(32)
        commitment = kzg.blob_to_kzg_commitment(blob)
        proof, y = kzg.compute_kzg_proof(blob, bls_field_to_bytes(5))
        with pytest.raises(KzgError):
            kzg.verify_kzg_proof(commitment, b"\x01" * 31, y, proof)


class TestSpans:
    def test_device_batch_is_one_tree_with_its_stages(self, kzg):
        """A DA check's batch is one span tree under ``da_check``: the host
        side in three phases, then the supervised device leg's four spans,
        whose durations still fill the flight record's stages."""
        from lighthouse_tpu import device_telemetry, tracing

        blobs = [make_blob(40 + i) for i in range(2)]
        commitments = [kzg.blob_to_kzg_commitment(b) for b in blobs]
        proofs = [kzg.compute_blob_kzg_proof(b, c)
                  for b, c in zip(blobs, commitments)]
        device = Kzg(kzg.setup, device=True)
        with tracing.span("da_check") as root:
            assert device.verify_blob_kzg_proof_batch(blobs, commitments, proofs)
        (batch,) = root.children
        assert batch.name == "kzg_blob_batch" and batch.fields["n_blobs"] == 2
        names = [c.name for c in batch.children]
        assert names == ["kzg_host_prep", "kzg_batch_setup", "kzg_batch_dispatch",
                         "kzg_batch_wait", "kzg_batch_verdict"]
        assert [c.name for c in batch.children[0].children] == [
            "kzg_parse", "kzg_challenge", "kzg_evaluate"]
        assert batch.children[1].fields["n_blobs"] == 2
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="kzg_batch", limit=1)[0]
        assert rec["trace_id"] == root.trace.trace_id
        assert set(rec["stages_s"]) == {"setup", "dispatch", "wait", "verdict"}
        assert rec["stages_s"]["setup"] == round(batch.children[1].duration, 6)
