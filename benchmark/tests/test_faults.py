"""Drive whole runs at a tiny size on the CPU, the harness's look for a chip
skipped, and see ``correct`` hold on a sound run and come out false under
the control and under each fault the cells can have (``benchmark/faults.py``).
One request in two is tampered, so a 9-second window holds both kinds."""

import time

import pytest

from benchmark import faults, run

SEED = 2 ** 31 + 3
CELLS = ("mainnet_1m.block_sigs", "mainnet_1m.blob_da")


def _run(root, cell, fault=None):
    def go():
        return run.run_cell(cell, SEED, 9.0, False, require_tpu=False,
                            root=root, workers=2, t0=time.perf_counter())

    if fault is None:
        return go()
    with faults.planted(fault):
        return go()


@pytest.fixture(scope="module", autouse=True)
def _pipeline():
    yield
    from lighthouse_tpu import device_pipeline

    device_pipeline.shutdown()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    info, result = _run(tiny_root, cell)
    assert result["correct"], result["checks"]
    assert info["tampered"] >= 2 and info["requests"] > info["tampered"]
    assert sum(info["compiles_in_window"].values()) == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"setup_s", cell.split(".")[1] == "block_sigs"
                                      and "block_verify_p50_ms" or "blob_da_p50_ms"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(tiny_root, cell, fault):
    info, result = _run(tiny_root, cell, fault)
    assert not result["correct"], (fault, result["checks"], info)
    failed = {k for k, c in result["checks"].items() if c["value"] > c["limit"]}
    want = {"host_route": {"host_fallbacks", "host_routed_batches"}}.get(
        fault, {"verdict_mismatches"})
    assert want & failed, failed


def test_trace_run_reports_per_layer_metrics(tiny_root):
    """--trace 1 on the CPU: the span readers report; the device readers
    find no chip plane and stay silent, never reading 0."""
    info, result = run.run_cell("mainnet_1m.block_sigs", SEED, 4.0, True,
                                require_tpu=False, root=tiny_root, workers=2,
                                t0=time.perf_counter())
    assert result["correct"]
    assert set(result["metrics"]) == {"pipeline_wait_ms.block", "marshal_ms.block"}
    assert result["device"]["window_s"] > 0
    assert "idle_gaps" in result["breakdown"]


def test_no_chip_no_result(tiny_root):
    with pytest.raises(run.NoChip):
        run.run_cell("mainnet_1m.block_sigs", SEED, 1.0, False, root=tiny_root,
                     workers=1)
