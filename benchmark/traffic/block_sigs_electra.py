"""Kind ``block_sigs_electra``: an Electra block's signature sets through
``bls.verify_signature_sets`` under ``work_context("block_import")``.

Under EIP-7549 one on-chain aggregate covers every committee of a slot that
voted for the same AttestationData.  A block holds
``MAX_ATTESTATIONS_ELECTRA`` aggregates over ``slots_per_block`` slots; each
committee of ``committee_size`` attesters splits its votes over a slot's
AttestationData by ``head_vote_split``, so a slot's aggregates carry
``MAX_COMMITTEES_PER_SLOT`` times each share of the split.  The sync
aggregate, the proposer and RANDAO follow.  Every message is fresh and a
block's keys are distinct, drawn from a pool of ``distinct_keys``; key ``i``
of the pool is ``[k0 + i]G1``, as in ``block_sigs``.
"""

from benchmark import ecref
from benchmark.traffic import gen
from benchmark.traffic.block_sigs import key_chunk  # noqa: F401  (a shared job)


def widths(sizes: dict) -> list:
    """Key count of each attestation aggregate, slot by slot."""
    per_data = [sizes["committees"] * share for share in sizes["head_vote_split"]]
    return per_data * sizes["slots_per_block"]


def bad_set(group: int, rng, sizes: dict) -> int:
    """The set a tampered block of tampered group ``group`` signs wrong: the
    widest aggregate of a seeded slot in every other group, and in the
    groups between another aggregate (seeded), the sync aggregate, the
    proposer and RANDAO in turn."""
    att = widths(sizes)
    n_data = len(sizes["head_vote_split"])
    if group % 2 == 0:
        return rng.randrange(sizes["slots_per_block"]) * n_data
    turn = (group // 2) % 4
    if turn == 0:
        return rng.choice([a for a in range(len(att)) if a % n_data])
    return len(att) + turn - 1


def make_block(sizes: dict, seed: int, stream: str, i: int, k0: int,
               tamper_every: int) -> dict:
    """One block's signature sets as plain numbers: ``layout`` holds
    ``(first key, key count, message index)`` per set, ``idx`` the pool
    index of every key, ``sigs`` each set's affine signature."""
    import numpy as np

    rng = gen.rng(seed, stream, i)
    layout, first = [], 0
    for m, count in enumerate(widths(sizes) + [sizes["sync_committee_size"], 1, 1]):
        layout.append((first, count, m))
        first += count
    idx = rng.sample(range(sizes["distinct_keys"]), first)
    msgs = [rng.randbytes(32) for _ in layout]
    group = gen.tampered(seed, stream, i, tamper_every)
    bad = None if group is None else bad_set(group, rng, sizes)
    sigs = []
    for j, (start, count, m) in enumerate(layout):
        k = count * k0 + sum(idx[start:start + count]) + (j == bad)
        sigs.append(ecref.g2_jac_mul(ecref.hash_to_g2_jac(msgs[m]), k % ecref.R))
    return {"layout": layout, "idx": np.asarray(idx, np.int32), "msgs": msgs,
            "sigs": sigs, "expect": bad is None, "tampered_set": bad}


class Traffic(gen.Kind):
    """One block at a time: a closed loop of one importer."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        super().__init__(cfg, mix, seed)
        self.sizes = {
            "committees": cfg["MAX_COMMITTEES_PER_SLOT"],
            "head_vote_split": list(cfg["head_vote_split"]),
            "slots_per_block": cfg["slots_per_block"],
            "sync_committee_size": cfg["SYNC_COMMITTEE_SIZE"],
            "distinct_keys": cfg["distinct_keys"],
        }
        if sum(cfg["head_vote_split"]) != cfg["committee_size"]:
            raise ValueError("the head-vote split must cover the committee")
        if len(widths(self.sizes)) != cfg["MAX_ATTESTATIONS_ELECTRA"]:
            raise ValueError("slots x AttestationData must fill the aggregates")
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
