"""Deneb KZG blob-proof batch verification timing (BASELINE.md config 5:
"6 blobs/block x 32 blocks" = 192 proofs/batch; the reference's
``crypto/kzg`` batch path over c-kzg).

Times the DEVICE batch program (``ops/kzg_device.py``) on CPU-jax with the
persistent cache, at the per-block (6) and scale (192) batch sizes, and
records the host-side baseline for the same batches.  Writes
``.perf/kzg_bench.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# --device runs on the accelerator JAX finds; the default pins the CPU.
if "--device" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")

from lighthouse_tpu.ops.compile_cache import configure_persistent_cache  # noqa: E402

configure_persistent_cache()


WIDTH = 64   # small domain: the device program's structure is identical
TAU = 0x5EC2E7


def main() -> None:
    from lighthouse_tpu.crypto.kzg import TrustedSetup
    from lighthouse_tpu.crypto.kzg.kzg import Kzg

    setup = TrustedSetup.insecure_dev_setup(width=WIDTH, secret=TAU)
    host = Kzg(setup, device=False)
    dev = Kzg(setup, device=True)

    def make_blob(seed: int) -> bytes:
        out = bytearray()
        for i in range(WIDTH):
            out += ((seed * 7919 + i * 104729) % (2**200)).to_bytes(32, "big")
        return bytes(out)

    results = []
    for n in (6, 192):
        blobs = [make_blob(i) for i in range(n)]
        commitments = [host.blob_to_kzg_commitment(b) for b in blobs]
        proofs = [host.compute_blob_kzg_proof(b, c)
                  for b, c in zip(blobs, commitments)]

        t0 = time.perf_counter()
        ok_host = host.verify_blob_kzg_proof_batch(blobs, commitments, proofs)
        host_secs = time.perf_counter() - t0

        t0 = time.perf_counter()
        ok_warm = dev.verify_blob_kzg_proof_batch(blobs, commitments, proofs)
        warm_secs = time.perf_counter() - t0
        t0 = time.perf_counter()
        ok_dev = dev.verify_blob_kzg_proof_batch(blobs, commitments, proofs)
        dev_secs = time.perf_counter() - t0

        assert ok_host and ok_warm and ok_dev
        rec = {
            "n_proofs": n,
            "host_secs": round(host_secs, 2),
            "device_warm_secs": round(warm_secs, 2),
            "device_exec_secs": round(dev_secs, 2),
            "device_proofs_per_sec": round(n / dev_secs, 2),
            "verifies": True,
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

    suffix = "_tpu" if jax.devices()[0].platform == "tpu" else ""
    out = os.path.join(HERE, ".perf", f"kzg_bench{suffix}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(json.dumps({"platform": jax.devices()[0].platform, "batches": results}) + "\n")


if __name__ == "__main__":
    main()
