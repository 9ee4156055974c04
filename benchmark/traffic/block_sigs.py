"""Kind ``block_sigs``: a block's signature sets through
``bls.verify_signature_sets`` under ``work_context("block_import")``.

A block holds ``MAX_ATTESTATIONS`` aggregates of ``committee_size`` keys over
``MAX_COMMITTEES_PER_SLOT`` distinct AttestationData, the sync aggregate,
the proposer and RANDAO; every message is fresh and a block's keys are
distinct, drawn from a pool of ``distinct_keys``.  Key ``i`` of the pool is
``[k0 + i]G1``, so a set's signature is one scalar multiplication of its
message point by the sum of its secret keys.
"""

import numpy as np

from benchmark import ecref
from benchmark.traffic import gen


def key_chunk(k0: int, start: int, count: int) -> list:
    """Public keys ``[k0 + i]G1`` for ``start <= i < start + count``."""
    return ecref.g1_chain(k0 + start, count)


def bad_set(group: int, rng, n_att: int) -> int:
    """The set a tampered block of tampered group ``group`` signs wrong: an
    attestation at a seeded row in every other group, and in the groups
    between the sync aggregate, the proposer and RANDAO in turn."""
    if group % 2 == 0:
        return rng.randrange(n_att)
    return n_att + (group // 2) % 3


def make_block(sizes: dict, seed: int, stream: str, i: int, k0: int,
               tamper_every: int) -> dict:
    """One block's signature sets as plain numbers: ``layout`` holds
    ``(first key, key count, message index)`` per set, ``idx`` the pool
    index of every key, ``sigs`` each set's affine signature."""
    rng = gen.rng(seed, stream, i)
    n_att, keys = sizes["attestations"], sizes["committee_size"]
    n_data, n_sync = sizes["attestation_data"], sizes["sync_committee_size"]
    layout = [(a * keys, keys, a * n_data // n_att) for a in range(n_att)]
    base = n_att * keys
    layout += [(base, n_sync, n_data), (base + n_sync, 1, n_data + 1),
               (base + n_sync + 1, 1, n_data + 2)]
    idx = rng.sample(range(sizes["distinct_keys"]), base + n_sync + 2)
    msgs = [rng.randbytes(32) for _ in range(n_data + 3)]
    group = gen.tampered(seed, stream, i, tamper_every)
    bad = None if group is None else bad_set(group, rng, n_att)
    hashes = {}
    sigs = []
    for j, (first, count, m) in enumerate(layout):
        if m not in hashes:
            hashes[m] = ecref.hash_to_g2_jac(msgs[m])
        k = count * k0 + sum(idx[first:first + count]) + (j == bad)
        sigs.append(ecref.g2_jac_mul(hashes[m], k % ecref.R))
    return {"layout": layout, "idx": np.asarray(idx, np.int32), "msgs": msgs,
            "sigs": sigs, "expect": bad is None, "tampered_set": bad}


class Traffic(gen.Kind):
    """One block at a time, by default: a closed loop of one importer."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        super().__init__(cfg, mix, seed)
        self.sizes = {
            "attestations": cfg["MAX_ATTESTATIONS"],
            "attestation_data": cfg["MAX_COMMITTEES_PER_SLOT"],
            "committee_size": cfg["committee_size"],
            "sync_committee_size": cfg["SYNC_COMMITTEE_SIZE"],
            "distinct_keys": cfg["distinct_keys"],
        }
        if cfg["MAX_ATTESTATIONS"] % cfg["MAX_COMMITTEES_PER_SLOT"]:
            raise ValueError("attestations must split evenly over the data")
        self.k0 = gen.rng(seed, "keys").randrange(1, 2 ** 32)
        self.pks = None

    def shared_jobs(self):
        n = self.sizes["distinct_keys"]
        step = -(-n // 16)
        return [("key_chunk", (self.k0, s, min(step, n - s)))
                for s in range(0, n, step)]

    def take_shared(self, results) -> None:
        from lighthouse_tpu.crypto.bls import api
        from lighthouse_tpu.crypto.bls.fields import Fq

        self.pks = [api.PublicKey((Fq(x), Fq(y)), _skip_checks=True)
                    for chunk in results for x, y in chunk]

    def item_job(self, stream: str, i: int):
        return ("make_block", (self.sizes, self.seed, stream, i, self.k0,
                               self.mix["tamper_every"]))

    def request(self, raw: dict):
        from lighthouse_tpu.crypto.bls import api
        from lighthouse_tpu.crypto.bls.fields import Fq2

        pks, idx = self.pks, raw["idx"].tolist()
        return [api.SignatureSet.multiple_pubkeys(
                    api.Signature((Fq2(*x), Fq2(*y))),
                    [pks[j] for j in idx[first:first + count]], raw["msgs"][m])
                for (first, count, m), (x, y) in zip(raw["layout"], raw["sigs"])]

    @staticmethod
    def call(sets) -> bool:
        from lighthouse_tpu import device_pipeline
        from lighthouse_tpu.crypto.bls import api

        with device_pipeline.work_context("block_import"):
            return api.verify_signature_sets(sets)

    @staticmethod
    def work(raw: dict) -> dict:
        return {"sets": len(raw["layout"]),
                "keys": sum(count for _, count, _ in raw["layout"])}
