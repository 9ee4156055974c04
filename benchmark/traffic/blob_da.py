"""Kind ``blob_da``: one block's blobs through ``Kzg(..., device=True)
.verify_blob_kzg_proof_batch``, the engine ``chain/da.py`` calls.

The setup's tau is drawn from the seed, so a commitment is ``[p(tau)]G1``
and a proof ``[(p(tau) - y) / (tau - z)]G1``; the check reads only
``[tau]G2``.  A tampered check has one blob whose commitment or proof is off.
"""

from functools import lru_cache

import numpy as np

from benchmark import ecref, kzgref
from benchmark.traffic import gen


@lru_cache(maxsize=2)
def _setup(tau: int, width: int) -> kzgref.KnownTauSetup:
    """One per worker process: its weights at tau take 4,096 inversions."""
    return kzgref.KnownTauSetup(tau, width)


def _blob_rng(seed: int, stream: str, i: int) -> np.random.Generator:
    return np.random.default_rng([seed, sum(stream.encode()), len(stream), i])


def g2_tau(tau: int):
    return ecref.g2_mul(ecref.G2, tau)


def make_blobs(seed: int, stream: str, i: int, tau: int, n_blobs: int,
               width: int, tamper_every: int) -> dict:
    """One block's blobs: commitments and proofs (the blob bytes are made
    again from the same seed where they are needed)."""
    rng = _blob_rng(seed, stream, i)
    blobs = [kzgref.blob_bytes(rng, width) for _ in range(n_blobs)]
    group = gen.tampered(seed, stream, i, tamper_every)
    bad = None if group is None else gen.rng(seed, stream, i).randrange(n_blobs)
    setup = _setup(tau, width)
    pairs = [setup.commit_and_prove(b, bad_commitment=(j == bad and group % 2 == 0),
                                    bad_proof=(j == bad and group % 2 == 1))
             for j, b in enumerate(blobs)]
    return {"commitments": [c for c, _ in pairs], "proofs": [p for _, p in pairs],
            "expect": bad is None, "tampered_blob": bad, "stream": stream, "i": i}


class Traffic(gen.Kind):
    """One block's blobs at a time, by default: a closed loop of one importer."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        super().__init__(cfg, mix, seed)
        self.n_blobs = cfg["MAX_BLOBS_PER_BLOCK"]
        self.width = cfg["FIELD_ELEMENTS_PER_BLOB"]
        self.tau = gen.rng(seed, "tau").randrange(1, ecref.R)
        self.kzg = None

    def shared_jobs(self):
        return [("g2_tau", (self.tau,))]

    def take_shared(self, results) -> None:
        from lighthouse_tpu.crypto.bls import curve
        from lighthouse_tpu.crypto.bls.fields import Fq2
        from lighthouse_tpu.crypto.kzg import Kzg, TrustedSetup

        (x, y), = results
        setup = TrustedSetup(g1_lagrange=[], width=self.width,
                             g2_monomial=[curve.G2, (Fq2(*x), Fq2(*y))])
        self.kzg = Kzg(setup, device=True)

    def item_job(self, stream: str, i: int):
        return ("make_blobs", (self.seed, stream, i, self.tau, self.n_blobs,
                               self.width, self.mix["tamper_every"]))

    def request(self, raw: dict):
        rng = _blob_rng(self.seed, raw["stream"], raw["i"])
        blobs = [kzgref.blob_bytes(rng, self.width) for _ in range(self.n_blobs)]
        return blobs, raw["commitments"], raw["proofs"]

    def call(self, req) -> bool:
        return self.kzg.verify_blob_kzg_proof_batch(*req)

    @staticmethod
    def work(raw: dict) -> dict:
        return {"blobs": len(raw["commitments"])}
