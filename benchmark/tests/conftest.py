"""A tiny benchmark in a temporary checkout root, for the CPU rehearsal:
the same harness, traffic generator and readers at sizes a test can hold."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {
    "SLOTS_PER_EPOCH": 32, "TARGET_COMMITTEE_SIZE": 128,
    "MAX_COMMITTEES_PER_SLOT": 2, "MAX_ATTESTATIONS": 4,
    "SYNC_COMMITTEE_SIZE": 8, "MAX_BLOBS_PER_BLOCK": 3,
    "FIELD_ELEMENTS_PER_BLOB": 16, "committee_size": 3, "distinct_keys": 64,
}


def make_root(tmp_path, tamper_every=None):
    """A checkout root whose BENCHMARK.json names tiny copies of the cells,
    with the real mixes, kinds and readers beside them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench_dir = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    for name in os.listdir(os.path.join(ROOT, "benchmark", "metrics")):
        if name.endswith(".py"):
            shutil.copy(os.path.join(ROOT, "benchmark", "metrics", name),
                        bench_dir / "metrics" / name)
    for name in os.listdir(os.path.join(ROOT, "benchmark", "traffic")):
        if name.endswith(".py"):
            shutil.copy(os.path.join(ROOT, "benchmark", "traffic", name),
                        bench_dir / "traffic" / name)
    for mix in ("block_sigs", "blob_da"):
        with open(os.path.join(ROOT, "benchmark", "traffic", f"{mix}.json")) as f:
            spec = json.load(f)
        spec["pool_items"] = 8
        if tamper_every:
            spec["tamper_every"] = tamper_every
        (bench_dir / "traffic" / f"{mix}.json").write_text(json.dumps(spec))
    for conf in bench["configs"]:
        conf["file"] = f"benchmark/configs/{conf['name']}.json"
        (tmp_path / conf["file"]).write_text(json.dumps(TINY))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """One tiny root for the session, so its compile cache is shared; one
    request in two is tampered, so a short window holds both kinds."""
    return make_root(tmp_path_factory.mktemp("bench"), tamper_every=2)
