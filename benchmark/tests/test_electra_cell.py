"""The Electra block cell at a tiny size on the CPU: its kind makes the same
items from the same seed, every item's verdict is the one it was made to
have (the program's host golden model agrees), a run is correct, and the
control and the half-batch fault turn ``correct`` false.  The top key bucket
is shrunk to 4 keys, so the widest aggregates span key rows as the real
cell's 26,560-key aggregate spans rows of 2,048."""

import json
import time

import pytest

from benchmark import faults, run
from benchmark.traffic import gen

from .conftest import ROOT, make_root

CELL = "mainnet_electra_1m.block_sigs_electra"
SEED = 2 ** 31 + 25
#: A seed whose third window block (a ~3 s block on the CPU) has a set past
#: the first half wrong, so ``half_batch`` leaves it out within the window.
SEED_FAULTS = 2 ** 31 + 32

#: 2 slots x 4 AttestationData over 2 committees of 7: aggregates of
#: 6 / 4 / 2 / 2 keys a slot, a 4-key sync aggregate, proposer, RANDAO.
TINY_ELECTRA = {
    "name": "mainnet_electra_1m", "MAX_COMMITTEES_PER_SLOT": 2,
    "MAX_ATTESTATIONS_ELECTRA": 8, "SYNC_COMMITTEE_SIZE": 4,
    "committee_size": 7, "head_vote_split": [3, 2, 1, 1],
    "slots_per_block": 2, "distinct_keys": 64,
}


@pytest.fixture(scope="module")
def electra_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("electra")
    root = make_root(tmp, tamper_every=2)
    (tmp / "benchmark/configs/mainnet_electra_1m.json").write_text(
        json.dumps(TINY_ELECTRA))
    spec = gen.load_mix("block_sigs_electra", ROOT)
    spec.update(pool_items=8, tamper_every=2)
    (tmp / "benchmark/traffic/block_sigs_electra.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def row_of_four(monkeypatch):
    from lighthouse_tpu.ops import verify

    monkeypatch.setattr(verify, "K_BUCKETS", (1, 2, 4))


@pytest.fixture(scope="module", autouse=True)
def _pipeline():
    yield
    from lighthouse_tpu import device_pipeline

    device_pipeline.shutdown()


def _traffic(seed=SEED):
    traffic = gen.open_traffic(TINY_ELECTRA, {"kind": "block_sigs_electra",
                                              "tamper_every": 2}, seed, ROOT)
    traffic.take_shared([gen.run_job(traffic.path, fn, a)
                         for fn, a in traffic.shared_jobs()])
    return traffic


def test_layout_of_the_published_block():
    """At the configuration's own sizes: 11 sets, 62,978 keys, the
    aggregates' widths as the head-vote split gives them."""
    with open(f"{ROOT}/benchmark/configs/mainnet_electra_1m.json") as f:
        cfg = json.load(f)
    traffic = gen.open_traffic(cfg, gen.load_mix("block_sigs_electra", ROOT),
                               SEED, ROOT)
    kind = gen.load_module(traffic.path)
    assert kind.widths(traffic.sizes) == [26560, 3136, 960, 576] * 2
    raw = {"layout": [(0, w, m) for m, w in enumerate(
        kind.widths(traffic.sizes) + [512, 1, 1])]}
    assert traffic.work(raw) == {"sets": 11, "keys": 62978}


def test_items_depend_on_seed_and_index_only():
    a = gen.make_inline(_traffic(), "window", 1)
    b = gen.make_inline(_traffic(), "window", 1)
    assert a["sigs"] == b["sigs"] and a["msgs"] == b["msgs"]
    assert a["idx"].tolist() == b["idx"].tolist()
    other = gen.make_inline(_traffic(SEED + 1), "window", 1)
    assert other["msgs"] != a["msgs"]


def test_items_valid_and_tampered_by_the_host_model():
    from lighthouse_tpu.crypto.bls.backends import host

    traffic = _traffic()
    seen = set()
    for i in range(4):
        raw = gen.make_inline(traffic, "window", i)
        sets = traffic.request(raw)
        assert [len(s.signing_keys) for s in sets] == [6, 4, 2, 2] * 2 + [4, 1, 1]
        keys = [k for s in sets for k in s.signing_keys]
        assert len(set(map(id, keys))) == len(keys), "keys repeat in a block"
        assert host.verify_signature_sets(sets) is raw["expect"]
        if not raw["expect"]:
            assert sets[raw["tampered_set"]].verify() is False
        seen.add(raw["expect"])
    assert seen == {True, False}


def test_tampered_sets_rotate():
    """Every other tampered block has a slot's widest aggregate wrong; the
    others another aggregate, the sync aggregate, the proposer and RANDAO
    in turn."""
    import random

    kind = gen.load_module(gen.kind_path("block_sigs_electra", ROOT))
    sizes = {"committees": 64, "head_vote_split": [415, 49, 15, 9],
             "slots_per_block": 2}
    rng = random.Random(1)
    bad = [kind.bad_set(g, rng, sizes) for g in range(16)]
    assert set(bad[0::2]) == {0, 4}
    odd = bad[1::2]
    assert odd[1::4] == [8, 8] and odd[2::4] == [9, 9] and odd[3::4] == [10, 10]
    assert all(b < 8 and b % 4 for b in odd[0::4])


def _run(root, fault=None, trace=False):
    def go():
        seed, seconds = (SEED, 4.0 if trace else 9.0) if fault is None else (
            SEED_FAULTS, 12.0)
        return run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                            root=root, workers=2, t0=time.perf_counter())

    if fault is None:
        return go()
    with faults.planted(fault):
        return go()


def test_sound_run_is_correct(electra_root, row_of_four):
    info, result = _run(electra_root)
    assert result["correct"], result["checks"]
    assert info["tampered"] >= 1 and info["requests"] > info["tampered"]
    assert sum(info["compiles_in_window"].values()) == 0
    assert set(result["metrics"]) == {"setup_s", "block_verify_p50_ms"}


@pytest.mark.parametrize("fault", ["accept_all", "half_batch"])
def test_fault_is_caught(electra_root, row_of_four, fault):
    third = gen.make_inline(_traffic(SEED_FAULTS), "window", 2)
    assert third["tampered_set"] >= 11 // 2  # past the half the fault keeps
    info, result = _run(electra_root, fault)
    assert not result["correct"], (fault, result["checks"], info)
    assert result["checks"]["verdict_mismatches"]["value"] > 0


def test_trace_run_reads_the_key_fill(electra_root, row_of_four):
    """--trace 1 on the CPU: the span readers and the key fill report (11
    sets over 13 rows of 4 in the 16-row bucket: 34 keys of 64 slots); the
    device readers find no chip plane and stay silent."""
    info, result = _run(electra_root, trace=True)
    assert result["correct"]
    assert result["metrics"]["key_fill_pct.electra"]["value"] == pytest.approx(
        100 * 34 / 64)
    assert "key_sum_device_ms.electra" not in result["metrics"]
    assert "key_sum_roofline.electra" not in result["metrics"]
