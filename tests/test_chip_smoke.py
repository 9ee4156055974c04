"""CPU rehearsal of ``chip_smoke.py``: each phase function at a tiny size,
through the same node assembly, with the same device-only guard.  Each
phase asserts its valid batch passes and its tampered copy fails; the
guard asserts the host-fallback counter stays 0 and every batch of the
phase ran on the device path."""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def node_assembly():
    from lighthouse_tpu import device_mesh, device_pipeline
    from lighthouse_tpu.client import assemble_device_path
    from lighthouse_tpu.crypto.bls.backends import backend_name, set_backend

    prev = backend_name()
    assemble_device_path("jax")
    yield
    device_pipeline.shutdown()
    device_mesh.reset_for_tests()
    set_backend(prev)


def _run(name, fn, *args):
    report = {}
    with chip_smoke.device_only(name, report):
        report.update(fn(*args))
    assert report["routes"], report
    return report


def test_node_phase():
    report = _run("node", chip_smoke.phase_node, 16, 3, "minimal")
    assert report["head_slot"] == 4  # the refused block's slot stays empty
    assert report["sync_keys"] == 32


def test_full_block_phase():
    # 4 sets (1 attestation, sync aggregate, proposer, RANDAO) x 32 keys:
    # the node phase's block bucket, so the CPU compiles it once
    report = _run("full_block", chip_smoke.phase_full_block, 1, 4, 32)
    assert report["sets"] == 4


def test_blobs_phase():
    report = _run("blobs", chip_smoke.phase_blobs, 2, 64)
    assert report["routes"] == {"kzg_batch:device": 2}


def test_epoch_phase():
    report = _run("epoch", chip_smoke.phase_epoch, 2**10)
    assert report["routes"] == {"epoch_boundary:device": 1}


def test_four_chips_phase():
    """dp4 over 4 of the conftest's 8 virtual devices: mesh and one-device
    verdicts agree, one shard per device."""
    report = _run("four_chips", chip_smoke.phase_four_chips, 5, 4, 32)
    assert report["shard_devices"] == [0, 1, 2, 3]
    assert report["shard_rows"] == [2, 2, 2, 2]


def test_guard_fails_on_host_fallback():
    """The guard itself: a batch the supervisor routes to the host fails
    the phase."""
    from lighthouse_tpu import device_supervisor

    def device_fn():
        raise device_supervisor.HostFallback("w_at_infinity")

    with pytest.raises(AssertionError, match="host fallback"):
        with chip_smoke.device_only("guard", {}):
            device_supervisor.run("guard_probe", device_fn,
                                  host_fn=lambda: True)


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok": true' not in captured.out
