"""Drive the beacon node's verification path once on one TPU chip.

``python chip_smoke.py`` assembles the node the way ``ClientBuilder.build``
does (``client.assemble_device_path``: BLS backend, compile cache, mesh,
device pipeline) and runs four phases in this one process, each checked
against the host golden model or a tampered copy:

- ``node``: mainnet preset, Capella at genesis, 1,024 interop validators;
  the harness imports 3 blocks with attestations and full 512-key sync
  aggregates, the head advancing every slot; a block with a wrong
  signature is refused.
- ``full_block``: one mainnet-shaped block's signature sets (128
  attestations over 488 keys, the 512-key sync aggregate, proposer and
  RANDAO) through ``bls.verify_signature_sets`` and the pipeline: True,
  and False with one signature swapped.
- ``blobs``: a Deneb block's 6 blobs of 4,096 field elements through
  ``Kzg.verify_blob_kzg_proof_batch(device=True)``: valid and tampered.
- ``epoch``: the fused epoch boundary at 2^20 validators through
  ``per_epoch._run_boundary``, bit-identical to the numpy composite.

Every phase fails if a batch was routed to the host: the
``device_batch_host_fallback_total`` counter must not move, and every
flight-recorder entry of the phase must have run on the device.  Earlier
lines of output carry each phase's details (compile seconds per bucket,
fq lowering, peak device bytes, route counts); the last line is the
driver's JSON.  Any platform but ``tpu`` is refused.

``--four-chips`` runs only the full-block batch sharded dp4 over the
device mesh, compared with the same batch on one device.

The phase functions take their sizes as arguments; ``tests/
test_chip_smoke.py`` runs them on the CPU at tiny sizes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import random
import sys
import time
import traceback


def _say(record: dict) -> None:
    print(json.dumps(record, sort_keys=True, default=str), flush=True)


def _fallback_total() -> float:
    from lighthouse_tpu import metrics

    return sum(metrics.DEVICE_HOST_FALLBACK.snapshot().values())


@contextlib.contextmanager
def device_only(phase: str, report: dict):
    """Fail ``phase`` if any batch it ran resolved on the host.  Fills
    ``report`` with the phase's route counts and the programs it compiled
    (op, bucket, compile seconds)."""
    from lighthouse_tpu import device_telemetry

    fallbacks0 = _fallback_total()
    seq0 = device_telemetry.FLIGHT_RECORDER.recorded_total
    compiled0 = {(e["op"], e["shape"])
                 for e in device_telemetry.COMPILE_CACHE.inventory()}
    yield
    recorder = device_telemetry.FLIGHT_RECORDER
    records = [r for r in recorder.recent(limit=recorder.capacity)
               if r["seq"] > seq0]
    routes: dict = {}
    for r in records:
        route = "host" if r["host_fallback"] else "device"
        key = f"{r['op']}:{route}"
        routes[key] = routes.get(key, 0) + 1
    report["routes"] = routes
    report["compiles"] = [
        {"op": e["op"], "bucket": e["shape"],
         "compile_s": e.get("compile_seconds")}
        for e in device_telemetry.COMPILE_CACHE.inventory()
        if (e["op"], e["shape"]) not in compiled0
    ]
    fallbacks = _fallback_total() - fallbacks0
    if fallbacks:
        raise AssertionError(f"{phase}: {fallbacks:g} host fallback(s)")
    host = [r for r in records if r["host_fallback"]]
    if host:
        raise AssertionError(f"{phase}: batches routed to the host: {host}")
    if not records:
        raise AssertionError(f"{phase}: no batch reached the device")


def _capella_spec(preset: str):
    from lighthouse_tpu.types.spec import ChainSpec, minimal_spec

    forks = dict(altair_fork_epoch=0, bellatrix_fork_epoch=0,
                 capella_fork_epoch=0, deneb_fork_epoch=None,
                 electra_fork_epoch=None)
    if preset == "minimal":
        return minimal_spec(**forks)
    return ChainSpec(**forks)


# ------------------------------------------------------------------ phases


def phase_node(n_validators: int, n_blocks: int, preset: str = "mainnet") -> dict:
    """The harness drives the chain: a block with a swapped signature is
    refused, then ``n_blocks`` blocks each carry a full sync aggregate and
    the previous slot's attestations."""
    from lighthouse_tpu.chain.harness import BeaconChainHarness

    h = BeaconChainHarness(validator_count=n_validators,
                           spec=_capella_spec(preset))
    sync_keys = h.spec.preset.sync_committee_size
    # a block signed over the wrong root: refused, head unchanged
    h.advance_slot()
    good = h.produce_signed_block()
    bad = type(good)(message=good.message,
                     signature=h.randao_reveal(h.head_state, 1, 0))
    head0 = h.head_root
    try:
        h.chain.process_block(bad, block_delay_seconds=1.0)
    except Exception as e:  # noqa: BLE001 — any refusal will do
        refused = type(e).__name__
    else:
        raise AssertionError("node: block with a wrong signature imported")
    assert h.head_root == head0, "node: refused block moved the head"
    h.attest_to_head()  # the empty slot's votes go into the first block
    attestations = []
    for _ in range(n_blocks):
        slot_before = h.chain.current_slot()
        (root,) = h.extend_chain(1)
        block = h.chain.get_block(root).message
        assert int(block.slot) == slot_before + 1, "node: slot skipped"
        assert h.head_root == root and int(h.head_state.slot) == int(block.slot), \
            f"node: head did not advance at slot {int(block.slot)}"
        bits = block.body.sync_aggregate.sync_committee_bits
        assert sum(bool(b) for b in bits) == sync_keys, "node: partial sync aggregate"
        attestations.append(len(block.body.attestations))
    assert all(attestations), f"node: blocks without attestations {attestations}"
    return {"validators": n_validators, "blocks": n_blocks,
            "head_slot": int(h.head_state.slot), "sync_keys": sync_keys,
            "attestations_per_block": attestations, "bad_block": refused}


def full_block_sets(n_att: int, att_keys: int, sync_keys: int,
                    distinct: int = 8, seed: int = 1):
    """A mainnet-shaped block's signature sets: ``n_att`` attestations over
    ``att_keys`` keys, the ``sync_keys``-key sync aggregate, proposer and
    RANDAO.  Keys and messages repeat across sets (the device work does not
    depend on the values), so the host signs ``distinct`` + 3 times."""
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.params import R

    rng = random.Random(seed)
    sks = [api.SecretKey(rng.randrange(1, R))
           for _ in range(max(att_keys, sync_keys))]
    pks = [sk.public_key() for sk in sks]
    att_sk = api.SecretKey(sum(sk.scalar for sk in sks[:att_keys]) % R)
    msgs = [bytes([seed, i % 256]) * 16 for i in range(distinct)]
    att_sigs = [att_sk.sign(m) for m in msgs]
    sets = [api.SignatureSet.multiple_pubkeys(
        att_sigs[i % distinct], pks[:att_keys], msgs[i % distinct])
        for i in range(n_att)]
    sync_sk = api.SecretKey(sum(sk.scalar for sk in sks[:sync_keys]) % R)
    sync_msg = b"sync-committee-root".ljust(32, b"\x00")
    sets.append(api.SignatureSet.multiple_pubkeys(
        sync_sk.sign(sync_msg), pks[:sync_keys], sync_msg))
    for i, tag in enumerate((b"block-root", b"randao-epoch")):
        msg = tag.ljust(32, b"\x00")
        sets.append(api.SignatureSet.single_pubkey(sks[i].sign(msg), pks[i], msg))
    return sets


def tampered(sets):
    """The same sets with the first attestation's signature swapped for a
    valid signature over another message."""
    from lighthouse_tpu.crypto.bls import api

    first, other = sets[0], sets[-1]
    bad = api.SignatureSet.multiple_pubkeys(
        other.signature, first.signing_keys, first.message)
    return [bad] + list(sets[1:])


def phase_full_block(n_att: int, att_keys: int, sync_keys: int) -> dict:
    from lighthouse_tpu import device_pipeline
    from lighthouse_tpu.crypto.bls import api

    sets = full_block_sets(n_att, att_keys, sync_keys)
    assert device_pipeline.routes(sets, None), "full block bypasses the pipeline"
    with device_pipeline.work_context("block_import"):
        ok = api.verify_signature_sets(sets)
        bad = api.verify_signature_sets(tampered(sets))
    assert ok is True, "full block: valid batch rejected"
    assert bad is False, "full block: tampered batch accepted"
    return {"sets": len(sets), "keys": sum(len(s.signing_keys) for s in sets)}


def phase_blobs(n_blobs: int, width: int, seed: int = 2) -> dict:
    from lighthouse_tpu.crypto.kzg import TrustedSetup
    from lighthouse_tpu.crypto.kzg.kzg import BLS_MODULUS, Kzg

    kzg = Kzg(TrustedSetup.insecure_dev_setup(width=width), device=True)
    rng = random.Random(seed)
    blobs = [b"".join(rng.randrange(BLS_MODULUS).to_bytes(32, "big")
                      for _ in range(width)) for _ in range(n_blobs)]
    commitments = [kzg.blob_to_kzg_commitment(b) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c)
              for b, c in zip(blobs, commitments)]
    ok = kzg.verify_blob_kzg_proof_batch(blobs, commitments, proofs)
    swapped = [proofs[-1]] + proofs[1:-1] + [proofs[0]]
    bad = kzg.verify_blob_kzg_proof_batch(blobs, commitments, swapped)
    assert ok is True, "blobs: valid batch rejected"
    assert bad is False, "blobs: tampered batch accepted"
    return {"blobs": n_blobs, "width": width}


def phase_epoch(n_validators: int, seed: int = 3) -> dict:
    import numpy as np

    from lighthouse_tpu.consensus import per_epoch
    from lighthouse_tpu.ops.shuffle_device import synthetic_plan

    plan = synthetic_plan(n_validators, seed)
    prev = (per_epoch._EPOCH_BACKEND, per_epoch._FUSED_BOUNDARY)
    per_epoch.set_epoch_backend("device")
    per_epoch.set_fused_boundary(True)
    try:
        got = per_epoch._run_boundary(plan, in_leak=False)
    finally:
        per_epoch.set_epoch_backend(prev[0])
        per_epoch.set_fused_boundary(prev[1])
    want = per_epoch._epoch_boundary_numpy(plan, in_leak=False)
    names = ("inactivity", "balance_delta", "effective_balance", "ejection",
             "queue", "activation", "shuffling", "proposer", "found")
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and np.array_equal(g, w), \
            f"epoch: {name} differs from the numpy composite"
    return {"validators": plan.n, "active": plan.m}


def phase_four_chips(n_att: int, att_keys: int, sync_keys: int,
                     n_devices: int = 4) -> dict:
    """The full-block batch sharded dp``n_devices`` through the mesh, and
    the same batch on one device: the verdicts agree, and each device
    holds its own shard of the marshalled batch."""
    from lighthouse_tpu import device_mesh, device_pipeline
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.ops.verify import build_device_batch

    sets = full_block_sets(n_att, att_keys, sync_keys)
    bad_sets = tampered(sets)
    verdicts = {}
    for label, spec in (("one_device", "0"), ("mesh", str(n_devices))):
        size = device_mesh.configure(spec)
        assert size == (0 if spec == "0" else n_devices), \
            f"mesh {spec}: configured {size} devices"
        with device_pipeline.work_context("block_import"):
            verdicts[label] = (api.verify_signature_sets(sets),
                               api.verify_signature_sets(bad_sets))
    assert verdicts["mesh"] == verdicts["one_device"] == (True, False), verdicts
    built = build_device_batch(sets)
    shards = built.args[0][0].addressable_shards
    devices = sorted(int(s.device.id) for s in shards)
    rows = [int(s.data.shape[0]) for s in shards]
    assert len(set(devices)) == n_devices == len(shards), \
        f"shards on devices {devices}"
    assert all(r == built.shape[0] // n_devices for r in rows), rows
    return {"verdicts": verdicts, "shard_devices": devices,
            "shard_rows": rows, "bucket": list(built.shape[:2])}


# -------------------------------------------------------------------- main


def _run_phase(name: str, fn, *args, **kwargs) -> dict:
    import jax

    from lighthouse_tpu.ops import fq

    report: dict = {"phase": name}
    t0 = time.perf_counter()
    with device_only(name, report):
        report.update(fn(*args, **kwargs))
    report["seconds"] = round(time.perf_counter() - t0, 3)
    report["fq_backend"] = fq.active_fq_backend()
    stats = jax.devices()[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["ok"] = True
    _say(report)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the full-block batch sharded dp4 vs one device")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform: {platform})",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from lighthouse_tpu import device_pipeline
    from lighthouse_tpu.client import assemble_device_path

    assemble_device_path("jax")
    try:
        if args.four_chips:
            _run_phase("four_chips", phase_four_chips, 128, 488, 512)
        else:
            _run_phase("node", phase_node, 1024, 3)
            _run_phase("full_block", phase_full_block, 128, 488, 512)
            _run_phase("blobs", phase_blobs, 6, 4096)
            _run_phase("epoch", phase_epoch, 2**20)
    except Exception as e:  # noqa: BLE001 — reported, then a nonzero exit
        traceback.print_exc()
        _say({"ok": False, "error": f"{type(e).__name__}: {e}"})
        return 1
    finally:
        device_pipeline.shutdown()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
