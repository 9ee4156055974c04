"""The benchmark's own EIP-4844 blob arithmetic, with a setup whose tau it knows.

With tau known, a commitment is ``[p(tau)]G1`` and a proof at ``z`` is
``[(p(tau) - y) / (tau - z)]G1``: one scalar multiplication each, in place of
a 4,096-point MSM.  The verifier reads only ``[tau]G2`` of the setup and does
the same work whatever tau is.  Challenges and the evaluation follow the
Deneb ``polynomial-commitments.md`` functions (``compute_challenge``,
``evaluate_polynomial_in_evaluation_form``) byte for byte.  Imports nothing of
the program.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

import numpy as np

from . import ecref

R = ecref.R
PRIMITIVE_ROOT_OF_UNITY = 7
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"


@lru_cache(maxsize=4)
def roots_brp(width: int) -> tuple:
    """The evaluation domain in bit-reversed order."""
    root = pow(PRIMITIVE_ROOT_OF_UNITY, (R - 1) // width, R)
    roots = [1] * width
    for i in range(1, width):
        roots[i] = roots[i - 1] * root % R
    bits = width.bit_length() - 1
    return tuple(roots[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(width))


def _batch_inv(xs):
    prefix = [1] * (len(xs) + 1)
    for i, x in enumerate(xs):
        prefix[i + 1] = prefix[i] * x % R
    acc = pow(prefix[-1], -1, R)
    out = [0] * len(xs)
    for i in range(len(xs) - 1, -1, -1):
        out[i] = prefix[i] * acc % R
        acc = acc * xs[i] % R
    return out


def _weights(z: int, width: int) -> list:
    """Barycentric weights: p(z) = sum_i p_i * weight_i for z off the domain."""
    roots = roots_brp(width)
    if z in roots:
        raise ValueError("evaluation point on the domain")
    scale = (pow(z, width, R) - 1) * pow(width, -1, R) % R
    return [w * d % R * scale % R
            for w, d in zip(roots, _batch_inv([(z - w) % R for w in roots]))]


def evaluate(poly, weights) -> int:
    return sum(p * w for p, w in zip(poly, weights)) % R


def blob_bytes(rng: np.random.Generator, width: int) -> bytes:
    """A blob of ``width`` field elements, each 31 random bytes under a zero
    byte: the packing rollups use, and always canonical."""
    raw = rng.integers(0, 256, size=(width, 32), dtype=np.uint8)
    raw[:, 0] = 0
    return raw.tobytes()


def blob_poly(blob: bytes) -> list:
    return [int.from_bytes(blob[i:i + 32], "big") for i in range(0, len(blob), 32)]


def challenge(blob: bytes, commitment: bytes, width: int) -> int:
    data = (FIAT_SHAMIR_PROTOCOL_DOMAIN + width.to_bytes(16, "big")
            + blob + commitment)
    return int.from_bytes(hashlib.sha256(data).digest(), "big") % R


class KnownTauSetup:
    """Commit and prove for one tau."""

    def __init__(self, tau: int, width: int):
        self.tau = tau % R
        self.width = width
        self._tau_weights = _weights(self.tau, width)

    def g2_tau(self):
        return ecref.g2_mul(ecref.G2, self.tau)

    def commit_and_prove(self, blob: bytes, bad_commitment: bool = False,
                         bad_proof: bool = False) -> tuple:
        """``(commitment, proof)`` as 48-byte compressed G1.  ``bad_*`` makes
        one of them a valid subgroup point that is off by ``G1``."""
        poly = blob_poly(blob)
        p_tau = evaluate(poly, self._tau_weights)
        commitment = ecref.g1_compress(ecref.g1_mul(ecref.G1, p_tau))
        z = challenge(blob, commitment, self.width)
        y = evaluate(poly, _weights(z, self.width))
        q = (p_tau - y) * pow((self.tau - z) % R, -1, R) % R
        proof = ecref.g1_compress(ecref.g1_mul(ecref.G1, q + int(bad_proof)))
        if bad_commitment:
            commitment = ecref.g1_compress(ecref.g1_mul(ecref.G1, p_tau + 1))
        return commitment, proof
