"""Unit tests for the mesh-sharding subsystem (device_mesh.py): env
parsing, registry-derived specs, padding arithmetic, per-device breakers,
reshard bookkeeping, and the pipeline target scaling — all host-side logic
on the conftest 8-device virtual CPU mesh, no device execution (the sharded
executions live in tests/test_multichip.py)."""

import numpy as np
import pytest

from lighthouse_tpu import device_mesh, device_telemetry


@pytest.fixture(autouse=True)
def _clean_mesh():
    device_mesh.reset_for_tests()
    yield
    device_mesh.reset_for_tests()


# ------------------------------------------------------------- configure


def test_configure_disabled_by_default(monkeypatch):
    monkeypatch.delenv(device_mesh.MESH_ENV, raising=False)
    assert device_mesh.configure() == 0
    assert not device_mesh.enabled()
    assert device_mesh.pad_rows(100) == 100  # identity when off


@pytest.mark.parametrize("spec", ["0", "off", ""])
def test_configure_explicit_off(spec):
    assert device_mesh.configure(spec) == 0
    assert not device_mesh.enabled()


def test_configure_auto_takes_all_devices():
    assert device_mesh.configure("auto") == 8
    assert device_mesh.enabled()
    assert device_mesh.size() == 8
    snap = device_mesh.summary()
    assert snap["devices"] == list(range(8))
    assert snap["full_size"] == 8
    assert all(b["state"] == "closed" for b in snap["breakers"])


def test_configure_numeric_clamps_to_available():
    assert device_mesh.configure("4") == 4
    assert device_mesh.configure("64") == 8  # more than available -> all


def test_single_device_request_falls_back_transparently():
    # ISSUE: "falls back to single-device transparently when <2 devices"
    assert device_mesh.configure("1") == 0
    assert not device_mesh.enabled()


def test_env_spec_respected(monkeypatch):
    monkeypatch.setenv(device_mesh.MESH_ENV, "auto")
    assert device_mesh.configure() == 8


# ------------------------------------------------------------- pad_rows


def test_pad_rows_rounds_to_mesh_multiple():
    device_mesh.configure("8")
    assert device_mesh.pad_rows(16) == 16
    assert device_mesh.pad_rows(100) == 104
    assert device_mesh.pad_rows(1) == 8
    device_mesh.force_trip(7)
    assert device_mesh.size() == 7
    assert device_mesh.pad_rows(128) == 133


# ---------------------------------------------------- per-device breakers


def test_force_trip_reshards_over_survivors():
    device_mesh.configure("auto")
    gen = device_mesh.generation()
    assert device_mesh.force_trip(3, reason="test")
    snap = device_mesh.summary()
    assert snap["size"] == 7
    assert 3 not in snap["devices"]
    assert snap["reshards_total"] == 1
    assert device_mesh.generation() > gen
    # idempotent: a dead device cannot trip twice
    assert not device_mesh.force_trip(3)
    assert device_mesh.summary()["reshards_total"] == 1


def test_note_failure_threshold_then_trip(monkeypatch):
    monkeypatch.setenv(device_mesh.DEVICE_FAILURE_THRESHOLD_ENV, "2")
    device_mesh.configure("auto")
    # unattributable error: the deterministic suspect is the highest-index
    # survivor — the 2-run scenario gate needs a reproducible order
    assert not device_mesh.note_failure("device_error")  # 1/2
    assert device_mesh.size() == 8
    assert device_mesh.note_failure("device_error")      # 2/2 -> trip
    snap = device_mesh.summary()
    assert snap["size"] == 7 and 7 not in snap["devices"]
    assert snap["breakers"][7]["state"] == "open"


def test_note_success_keeps_thresholds_consecutive(monkeypatch):
    """A clean dispatch between two transients resets the closed breakers:
    unattributable failures hours apart must not ratchet healthy devices
    out of the mesh (the suspect is always the highest-index survivor)."""
    monkeypatch.setenv(device_mesh.DEVICE_FAILURE_THRESHOLD_ENV, "2")
    device_mesh.configure("auto")
    assert not device_mesh.note_failure("device_error")  # 1/2
    device_mesh.note_success()                           # counter clears
    assert not device_mesh.note_failure("device_error")  # 1/2 again
    assert device_mesh.size() == 8
    # an OPEN breaker stays open through successes (re-admission is
    # operator-driven)
    device_mesh.force_trip(7)
    device_mesh.note_success()
    assert device_mesh.summary()["breakers"][7]["state"] == "open"
    assert device_mesh.size() == 7


def test_grow_rows_pads_and_is_identity_at_size():
    arr = np.arange(12, dtype=np.int64).reshape(3, 4)
    assert device_mesh.grow_rows(arr, 3, 0) is arr
    grown = device_mesh.grow_rows(arr, 5, 7)
    assert grown.shape == (5, 4)
    assert np.array_equal(grown[:3], arr)
    assert (grown[3:] == 7).all()


def test_note_failure_parses_device_from_error():
    device_mesh.configure("auto")
    err = RuntimeError("transfer to TPU_3 failed: device or resource busy")
    assert device_mesh.STATE.suspect_device(err) == 3
    err2 = RuntimeError("device 5: halted")
    assert device_mesh.STATE.suspect_device(err2) == 5
    # an id the mesh does not contain falls back to the suspect
    err3 = RuntimeError("device 42 exploded")
    assert device_mesh.STATE.suspect_device(err3) == 7


def test_mesh_exhaustion_disables_mesh():
    device_mesh.configure("2")
    assert device_mesh.enabled()
    device_mesh.force_trip(1)
    # below 2 survivors the mesh is off: single-device dispatch, and past
    # it the op breaker's host fallback — the terminal degradation state
    assert not device_mesh.enabled()
    assert device_mesh.pad_rows(100) == 100


def test_reshard_invalidates_meshed_compile_mirror():
    device_mesh.configure("auto")
    device_telemetry.COMPILE_CACHE.clear()
    device_telemetry.note_dispatch("bls_verify", (16, 2), 1.0, mesh=8)
    device_telemetry.note_dispatch("bls_verify", (16, 2), 1.0)  # unsharded
    assert device_telemetry.COMPILE_CACHE.seen("bls_verify", (16, 2), mesh=8)
    device_mesh.force_trip(0)
    # the old topology's AOT/jit state is invalid; the unsharded entry stays
    assert not device_telemetry.COMPILE_CACHE.seen("bls_verify", (16, 2), mesh=8)
    assert device_telemetry.COMPILE_CACHE.seen("bls_verify", (16, 2))


# --------------------------------------------------------- target scaling


def test_scale_target_shrinks_with_mesh():
    assert device_mesh.scale_target(4096) == 4096  # mesh off: identity
    device_mesh.configure("auto")
    assert device_mesh.scale_target(4096) == 4096  # full strength
    device_mesh.force_trip(7)
    assert device_mesh.scale_target(4096) == 4096 * 7 // 8
    device_mesh.force_trip(6)
    assert device_mesh.scale_target(4096) == 4096 * 6 // 8


def test_pipeline_snapshot_reports_effective_target():
    from lighthouse_tpu.device_pipeline import DevicePipeline

    device_mesh.configure("auto")
    pipe = DevicePipeline("bls_verify", target_sets=64,
                         verify_flat_fn=lambda sets: True)
    try:
        assert pipe.snapshot()["effective_target_sets"] == 64
        device_mesh.force_trip(7)
        assert pipe.snapshot()["effective_target_sets"] == 56
    finally:
        pipe.shutdown(timeout=5.0)


# ------------------------------------------------------------ ShardedEntry


def test_sharded_entry_requires_registry_declaration():
    with pytest.raises(KeyError):
        device_mesh.ShardedEntry("lighthouse_tpu/ops/nope.py:missing",
                                 lambda x: x)


def test_sharded_entry_rejects_undeclared_parameters():
    with pytest.raises(ValueError):
        device_mesh.ShardedEntry(
            "lighthouse_tpu/ops/sha256_device.py:_sha256_64byte_batch",
            lambda words, rogue: words,
        )


def test_sharded_entry_specs_derive_from_registry():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from lighthouse_tpu.ops import epoch_device, verify

    mesh = Mesh(np.array(jax.devices()), (device_mesh.AXIS,))
    bls = device_mesh.ShardedEntry(
        verify.ENTRY_KEY, verify._device_verify.__wrapped__)
    specs = bls.in_shardings(mesh)
    assert len(specs) == 6                            # ... live, rows
    assert all(s.spec == P("dp") for s in specs)      # all batched
    assert bls.out_sharding(mesh).spec == P()         # batch-reduced output

    epoch = device_mesh.ShardedEntry(
        epoch_device.ENTRY_KEY, epoch_device._deltas_kernel.__wrapped__,
        static_argnames=("in_leak",))
    specs = epoch.in_shardings(mesh)
    assert len(specs) == 14
    assert [s.spec for s in specs[:7]] == [P("dp")] * 7   # batched args
    assert [s.spec for s in specs[7:]] == [P()] * 7       # replicated scalars
    assert epoch.out_sharding(mesh).spec == P("dp")       # per-validator out


def test_shard_live_counts_pack_padding_on_last_shards():
    device_mesh.configure("auto")
    entry = None
    from lighthouse_tpu.ops import verify

    entry = device_mesh.ShardedEntry(
        verify.ENTRY_KEY, verify._device_verify.__wrapped__)
    assert entry.shard_live_counts(100, 128) == [16, 16, 16, 16, 16, 16, 4, 0]
    assert sum(entry.shard_live_counts(100, 128)) == 100
    device_mesh.force_trip(7)
    assert entry.shard_live_counts(12, 21) == [3, 3, 3, 3, 0, 0, 0]


def test_mesh_pad_of_key_rows_and_their_index(monkeypatch):
    """Where sets span key rows, the mesh pad grows the set axis (with
    ``rows`` at -1: no key row) and the key-row axis, each to its own
    multiple of the mesh size, with the neutral fills ``build_batch`` uses."""
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.backends.host import _rand_scalars
    from lighthouse_tpu.ops import ec, verify

    monkeypatch.setattr(verify, "K_BUCKETS", (1, 2, 4))
    sks = [api.SecretKey(7000 + i) for i in range(9)]
    agg = api.SecretKey(sum(sk.scalar for sk in sks))
    sets = [api.SignatureSet.multiple_pubkeys(
        agg.sign(b"pad"), [sk.public_key() for sk in sks], b"pad")]
    host_batch = verify.build_batch(sets, _rand_scalars(1, b"pad"))
    assert verify.bucket_shape(host_batch) == (1, 4, 4, 4)
    device_mesh.configure("auto")
    padded = verify.PROGRAM.pad(host_batch, device_mesh.pad_rows(1))
    assert verify.bucket_shape(padded) == (8, 4, 8, 4)
    pk, sig, msg, wbits, live, rows = padded
    assert rows[0].tolist() == [0, 1, 2, -1] and (rows[1:] == -1).all()
    assert live.tolist() == [True] + [False] * 7 and not wbits[1:].any()
    id1 = ec.g1_to_limbs(None)
    for c in range(3):
        assert (pk[c][:4] == host_batch[0][c]).all()
        assert (pk[c][4:] == id1[c]).all()           # identity key rows
