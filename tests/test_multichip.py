"""Multi-device sharding tests on the 8-CPU virtual mesh (conftest.py).

Proves inside the suite what the driver's ``dryrun_multichip`` checks
externally: the fused batch-verification program compiles and runs correctly
when the signature-set batch axis is sharded over a ``jax.sharding.Mesh``
(the data-parallel analog of the reference's rayon chunking,
block_signature_verifier.rs:396-404), with XLA inserting the cross-device
collectives for the G2 tree-sum and Miller-product reductions.
"""

import functools
import os
import subprocess
import sys

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DEVICES = 8
N_SETS = 16


@functools.lru_cache(maxsize=None)
def _sharded_fn():
    from lighthouse_tpu.ops.verify import _device_verify

    devices = jax.devices()
    assert len(devices) >= N_DEVICES, "conftest must provision 8 virtual CPU devices"
    mesh = Mesh(np.array(devices[:N_DEVICES]), ("dp",))
    dp = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(_device_verify.__wrapped__, out_shardings=(repl, repl))
    return fn, dp


def _shard_args(batch, dp):
    pk, sig, msg, wbits, live, rows = batch
    shard = lambda x: jax.device_put(x, dp)
    return (
        tuple(shard(c) for c in pk),
        tuple(shard(c) for c in sig),
        tuple(shard(c) for c in msg),
        shard(wbits),
        shard(live),
        None if rows is None else shard(rows),
    )


def test_sharded_verify_on_mesh():
    from __graft_entry__ import _build_example
    from lighthouse_tpu.ops.pairing import fe_is_one

    fn, dp = _sharded_fn()
    batch = _build_example(n_sets=N_SETS, n_keys=2)
    fe, w_z = fn(*_shard_args(batch, dp))
    jax.block_until_ready((fe, w_z))
    assert fe_is_one(fe)


def test_sharded_verify_rejects_bad_signature():
    """Sharded path must reject a corrupted batch (same shape → same program)."""
    from __graft_entry__ import _build_example
    from lighthouse_tpu.ops.pairing import fe_is_one

    fn, dp = _sharded_fn()
    pk, sig, msg, wbits, live, rows = _build_example(n_sets=N_SETS, n_keys=2)
    # Corrupt the hash points: swap x and y limb blocks.
    batch = (pk, sig, (msg[1], msg[0]), wbits, live, rows)
    fe, _ = fn(*_shard_args(batch, dp))
    assert not fe_is_one(fe)


def test_sharded_16_sets_bit_parity_vs_unsharded():
    """VERDICT r4 item 8: a full 16-set batch on the 8-device mesh, the
    program ``test_sharded_verify_on_mesh`` compiles.

    Per-device sharding is ASSERTED on the inputs (8 addressable shards on
    the batch axis), and the mesh program's FE output limbs must be
    BIT-IDENTICAL to the single-device program on the same arrays — the
    cross-device collective structure (G2 tree-sum, Miller line-product
    reductions) must not perturb a single limb."""
    from __graft_entry__ import _build_example
    from lighthouse_tpu.ops.pairing import fe_is_one
    from lighthouse_tpu.ops.verify import _device_verify

    fn, dp = _sharded_fn()
    batch = _build_example(n_sets=N_SETS, n_keys=2, seed=21)
    sharded_args = _shard_args(batch, dp)
    # sharding asserted: the batch axis is split across all 8 devices
    pk0 = sharded_args[0][0]
    assert len(pk0.sharding.device_set) == N_DEVICES
    shard_rows = sorted(s.data.shape[0] for s in pk0.addressable_shards)
    assert shard_rows == [2] * N_DEVICES, shard_rows

    fe_mesh, wz_mesh = fn(*sharded_args)
    jax.block_until_ready((fe_mesh, wz_mesh))
    assert fe_is_one(fe_mesh)

    fe_one, wz_one = _device_verify(*batch)
    jax.block_until_ready((fe_one, wz_one))
    assert np.array_equal(np.asarray(fe_mesh), np.asarray(fe_one)), (
        "mesh FE limbs diverge from the single-device program")
    assert np.array_equal(np.asarray(wz_mesh), np.asarray(wz_one))


def test_sharded_uneven_live_batch_13_over_8():
    """An UNEVEN 13-set batch over 8 devices.

    XLA rejects non-divisible jit input shardings by design (static
    shapes), so raw 13-over-8 sharding is impossible; the framework's
    uneven-batch mechanism is the BUCKET layer: ``build_batch(13 sets)``
    pads to the 16 bucket with identity points + dead ``live`` rows.  This
    test proves that path end to end on the mesh: the padded batch shards
    2 rows/device (the seventh device holding one live row and one pad
    row, the last only padding), the padding flows through every
    cross-device collective as exact neutral elements, the result
    verifies, and the FE limbs are bit-identical to the single-device
    program."""
    from __graft_entry__ import _build_example
    from lighthouse_tpu.ops.pairing import fe_is_one
    from lighthouse_tpu.ops.verify import _device_verify

    fn, dp = _sharded_fn()
    batch = _build_example(n_sets=13, n_keys=2, seed=33)
    live = np.asarray(batch[4])
    assert live.shape[0] == N_SETS and live.sum() == 13  # bucket-padded
    sharded_args = _shard_args(batch, dp)
    pk0 = sharded_args[0][0]
    assert len(pk0.sharding.device_set) == N_DEVICES
    shard_rows = [s.data.shape[0] for s in pk0.addressable_shards]
    assert shard_rows == [2] * N_DEVICES, shard_rows

    fe_mesh, wz_mesh = fn(*sharded_args)
    jax.block_until_ready((fe_mesh, wz_mesh))
    assert fe_is_one(fe_mesh)

    fe_one, _ = _device_verify(*batch)
    jax.block_until_ready(fe_one)
    assert np.array_equal(np.asarray(fe_mesh), np.asarray(fe_one))

    # and a corrupted LIVE row still fails while dead rows stay inert
    pk, sig, msg, wbits, live_arr, rows = batch
    bad = (pk, sig, (msg[1], msg[0]), wbits, live_arr, rows)
    fe_bad, _ = fn(*_shard_args(bad, dp))
    assert not fe_is_one(fe_bad)


def test_sharded_bit_parity_vs_host_golden():
    """The mesh program's FE equals the HOST golden model's final
    exponentiation value exactly (not just is_one agreement): the full
    limb-decode of the mesh output is compared against the host-integer
    pairing product for the same sets and weights."""
    from __graft_entry__ import _build_example
    from lighthouse_tpu.crypto.bls import host_projective as hpp
    from lighthouse_tpu.crypto.bls.backends.host import _rand_scalars
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.crypto.bls.pairing import final_exponentiation
    from lighthouse_tpu.crypto.bls.params import DST
    from lighthouse_tpu.crypto.bls import api, curve
    from lighthouse_tpu.ops import tower
    import random as _random

    fn, dp = _sharded_fn()
    n_sets, n_keys = N_SETS, 2

    # Rebuild the same sets _build_example makes, to drive the host model.
    rng = _random.Random(7)
    from lighthouse_tpu.crypto.bls.params import R
    sks = [api.SecretKey(rng.randrange(1, R)) for _ in range(n_keys)]
    pks = [sk.public_key() for sk in sks]
    agg_sk = api.SecretKey(sum(sk.scalar for sk in sks) % R)
    sets = []
    for i in range(n_sets):
        msg = (i.to_bytes(2, "big") + bytes([7])) * 10 + b"\x00\x00"
        sets.append(api.SignatureSet.multiple_pubkeys(agg_sk.sign(msg), pks, msg))
    rands = _rand_scalars(len(sets), seed=b"graft-entry")

    from lighthouse_tpu.ops.verify import build_batch
    batch = build_batch(sets, rands)
    fe_mesh, _ = fn(*_shard_args(batch, dp))
    jax.block_until_ready(fe_mesh)

    # Host golden: f = prod_i miller([r_i]aggpk_i, H(m_i)) * miller(-g1, W)
    f = None
    w = None
    for s, r in zip(sets, rands):
        h = hash_to_g2(s.message, DST)
        aggpk = None
        for key in s.signing_keys:
            aggpk = curve.add(aggpk, key.point)
        p = curve.mul(aggpk, r)
        fi = hpp.miller_loop_projective(p, h)
        f = fi if f is None else f * fi
        w = curve.add(w, curve.mul(s.signature.point, r))
    neg_g1 = (curve.G1[0], -curve.G1[1])
    f = f * hpp.miller_loop_projective(neg_g1, w)
    expected = final_exponentiation(f)
    assert tower.fq12_from_limbs(np.asarray(fe_mesh)) == expected, (
        "mesh FE value diverges from the host golden model")


# ------------------------------------------------- the SUPERVISED mesh path
#
# Everything above drives the raw jitted program by hand.  These tests run
# the PRODUCTION dispatch stack — device_mesh.ShardedEntry derives the
# specs from ops/batch_axes.py, the supervisor wraps the dispatch, the
# flight recorder carries the per-shard occupancy view — and assert the
# sharded verdicts/bytes match the single-device path exactly.


import contextlib

import pytest


@contextlib.contextmanager
def _mesh(spec="auto"):
    from lighthouse_tpu import device_mesh

    size = device_mesh.configure(spec)
    assert size == N_DEVICES, "conftest must provision 8 virtual CPU devices"
    try:
        yield size
    finally:
        device_mesh.reset_for_tests()


def _example_sets(n_sets, n_keys=2, seed=7):
    import random

    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.params import R

    rng = random.Random(seed)
    sks = [api.SecretKey(rng.randrange(1, R)) for _ in range(n_keys)]
    pks = [sk.public_key() for sk in sks]
    agg = api.SecretKey(sum(sk.scalar for sk in sks) % R)
    sets = []
    for i in range(n_sets):
        msg = (i.to_bytes(2, "big") + bytes([seed & 0xFF])) * 10 + b"\x00\x00"
        sets.append(api.SignatureSet.multiple_pubkeys(agg.sign(msg), pks, msg))
    return sets


def test_supervised_sharded_bls_verify_matches_single_device():
    """The production entry (`verify_signature_sets_device` — supervisor,
    telemetry, the registry-derived placer) on the mesh: same verdict as
    unsharded, per-shard live counts recorded, padding on the last shards
    (12 live sets in the 16-bucket over 8 devices)."""
    from lighthouse_tpu import device_telemetry
    from lighthouse_tpu.ops.verify import verify_signature_sets_device

    sets = _example_sets(12)
    assert verify_signature_sets_device(sets, seed=b"mesh-par") is True
    with _mesh():
        assert verify_signature_sets_device(sets, seed=b"mesh-par") is True
        rec = device_telemetry.FLIGHT_RECORDER.recent(1)[0]
    assert rec["shape"] == "16x2@dp8"
    assert rec["mesh"] == N_DEVICES
    assert rec["shard_live"] == [2, 2, 2, 2, 2, 2, 0, 0]
    assert not rec["host_fallback"]
    assert rec["occupancy_per_shard"][-1] == 0.0  # padding lands last


def test_supervised_sharded_bls_rejects_bad_set():
    """A corrupted set fails on the mesh exactly as it fails unsharded
    (same program shape -> same cached executables as the test above)."""
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.ops.verify import verify_signature_sets_device

    sets = _example_sets(12)
    bad = _example_sets(1, seed=9)[0]
    sets[5] = api.SignatureSet.multiple_pubkeys(
        bad.signature, bad.signing_keys, b"a different message entirely")
    assert verify_signature_sets_device(sets, seed=b"mesh-par") is False
    with _mesh():
        assert verify_signature_sets_device(sets, seed=b"mesh-par") is False


def test_supervised_sharded_wide_sets_match_single_device(monkeypatch):
    """Sets wider than the top key bucket (shrunk to 4 keys) on the mesh:
    the key rows and their index shard with the batch, the set sums gather
    across shards, and the verdicts match the single-device path and the
    host golden model for a sound and a tampered batch."""
    from lighthouse_tpu import device_telemetry
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.backends import host
    from lighthouse_tpu.ops import verify

    monkeypatch.setattr(verify, "K_BUCKETS", (1, 2, 4))
    sets = _example_sets(2, n_keys=9, seed=13)
    bad = list(sets)
    bad[1] = api.SignatureSet(sets[0].signature, sets[1].message,
                              sets[1].signing_keys)
    for batch, want in ((sets, True), (bad, False)):
        assert host.verify_signature_sets(batch, seed=b"mesh-rows") is want
        assert verify.verify_signature_sets_device(batch, seed=b"mesh-rows") is want
        with _mesh():
            assert verify.verify_signature_sets_device(
                batch, seed=b"mesh-rows") is want
            rec = device_telemetry.FLIGHT_RECORDER.recent(1)[0]
        assert rec["shape"] == "8x4x8x4@dp8" and not rec["host_fallback"]


def test_sharded_sha256_pairs_bit_identical_uneven():
    """The supervised pair-hash on the mesh returns byte-identical digests
    for a NON-divisible live count (100 blocks -> 256 bucket over 8), with
    the padding accounted on the last shards."""
    from lighthouse_tpu import device_telemetry
    from lighthouse_tpu.ops import sha256_device

    data = bytes(range(256)) * 25  # 100 64-byte blocks
    host = sha256_device.hash_pairs_device(data)
    with _mesh():
        meshed = sha256_device.hash_pairs_device(data)
        rec = device_telemetry.FLIGHT_RECORDER.recent(1)[0]
    assert meshed == host
    assert rec["shape"] == "256@dp8"
    assert rec["shard_live"] == [32, 32, 32, 4, 0, 0, 0, 0]


def test_sharded_epoch_deltas_bit_identical_uneven():
    """The epoch kernel on the mesh — registry-wide participating sums
    completing through psums — returns bit-identical int64 arrays for a
    100-validator registry (buckets to 256, never-active pad rows)."""
    from lighthouse_tpu.ops import epoch_device

    rng = np.random.default_rng(5)
    n = 100

    class _Arrays:
        effective_balance = rng.integers(1, 32_000_000_000, n)
        activation_epoch = rng.integers(0, 5, n)
        exit_epoch = rng.integers(6, 100, n)
        withdrawable_epoch = rng.integers(6, 200, n)
        slashed = rng.random(n) < 0.1

    class _Spec:
        effective_balance_increment = 1_000_000_000
        inactivity_score_bias = 4
        inactivity_score_recovery_rate = 16

    kw = dict(
        previous_epoch=4, in_leak=False, base_reward_per_increment=512,
        total_active_balance=int(_Arrays.effective_balance.sum()),
        quotient=67_108_864, spec=_Spec(),
    )
    prev_part = rng.integers(0, 8, n)
    inact = rng.integers(0, 10, n)
    host = epoch_device.epoch_deltas_device(_Arrays, prev_part, inact, **kw)
    with _mesh():
        meshed = epoch_device.epoch_deltas_device(
            _Arrays, prev_part, inact, **kw)
    for h, m in zip(host, meshed):
        assert np.array_equal(h, m)
        assert m.shape == (n,)  # the mesh pad is sliced back off


@pytest.mark.slow
def test_sharded_kzg_batch_verdict_and_fe_identical():
    """kzg_batch on the mesh: the blob-axis lincombs psum across devices
    and the supervised verdict matches single-device (fabricated points —
    verdict equality is the contract, the host golden model decides)."""
    from lighthouse_tpu import device_telemetry
    from lighthouse_tpu.crypto.bls import curve
    from lighthouse_tpu.crypto.bls.params import R
    from lighthouse_tpu.ops import kzg_device

    npts = 5
    c_pts = [curve.mul(curve.G1, i + 2) for i in range(npts)]
    p_pts = [curve.mul(curve.G1, 3 * i + 1) for i in range(npts)]
    r_powers = [pow(7, i, R) for i in range(npts)]
    zs = [11 + i for i in range(npts)]
    ys = [5 + 2 * i for i in range(npts)]
    g2_tau = curve.mul(curve.G2, 1234567)
    host = kzg_device.verify_kzg_proof_batch_device(
        c_pts, p_pts, r_powers, zs, ys, g2_tau)
    with _mesh():
        meshed = kzg_device.verify_kzg_proof_batch_device(
            c_pts, p_pts, r_powers, zs, ys, g2_tau)
        rec = device_telemetry.FLIGHT_RECORDER.recent(1)[0]
    assert meshed == host
    assert rec["shape"] == "8@dp8"
    assert rec["shard_live"] == [1, 1, 1, 1, 1, 0, 0, 0]
    assert not rec["host_fallback"]


@pytest.mark.slow
def test_per_device_breaker_trip_reshards_mid_op(monkeypatch):
    """The acceptance path: a device failure mid-op trips that device's
    breaker, the mesh re-shards to 7 survivors, the SAME batch retries on
    the shrunk topology (re-padded 16 -> 21 rows) and the verdict is
    identical to single-device — no host fallback, no op-breaker trip."""
    from lighthouse_tpu import device_mesh, device_supervisor, device_telemetry, fault_injection
    from lighthouse_tpu.ops.verify import verify_signature_sets_device

    monkeypatch.setenv(device_mesh.DEVICE_FAILURE_THRESHOLD_ENV, "1")
    sets = _example_sets(12)
    assert verify_signature_sets_device(sets, seed=b"trip") is True
    device_supervisor.reset_for_tests()
    with _mesh():
        # exactly ONE dispatch fault: the charge trips the suspect device
        # (threshold 1), the mesh re-shards, and the retry must succeed
        for plan in fault_injection.parse_spec(
                "device.dispatch[op=bls_verify]=error:first_n=1"):
            fault_injection.REGISTRY.install(plan)
        try:
            assert verify_signature_sets_device(sets, seed=b"trip") is True
        finally:
            fault_injection.clear()
        rec = device_telemetry.FLIGHT_RECORDER.recent(1)[0]
        snap = device_mesh.summary()
    assert snap["size"] == N_DEVICES - 1
    assert snap["reshards_total"] == 1
    assert rec["shape"] == "21x2@dp7"
    assert rec["shard_live"] == [3, 3, 3, 3, 0, 0, 0]
    assert not rec["host_fallback"]
    # the op-level breaker never engaged: the device layer absorbed it
    assert device_supervisor.breaker_state("bls_verify") == "closed"


def test_dryrun_multichip_subprocess():
    """The driver-facing entry point must succeed from an arbitrary parent env.

    Simulates the round-1 failure mode: dryrun_multichip must pass regardless
    of the parent's JAX platform config, because it re-execs a CPU-forced
    child with the device count fixed before interpreter start.
    """
    code = (
        "import os, sys; sys.path.insert(0, %r); "
        "import __graft_entry__ as g; g.dryrun_multichip(4); print('PARENT-OK')" % REPO
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # parent needs a working jax only for import
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        timeout=600,
    )
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out
    assert "PARENT-OK" in out
