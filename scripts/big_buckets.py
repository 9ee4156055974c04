"""Deliberately compile + execute the headline device buckets on CPU-jax.

VERDICT r4 item 3: the 128x32 and 4096x32 buckets had "only ever been
attempted inside timed-out bench children" — a shape-dependent compile
blowup or memory overflow at those shapes would surface in the round's one
bench shot instead of in CI.  This driver runs them on purpose with the
persistent compile cache, asserts verify-true, and records compile/exec
seconds to ``.perf/big_buckets.json`` (committed).

Reference semantics: crypto/bls/src/impls/blst.rs:35-117 (the 128-sig bench
config and the 4,096-attestation scale config of BASELINE.md).

Usage:  python scripts/big_buckets.py [--sets 128 4096] [--keys 32]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from lighthouse_tpu.ops.compile_cache import configure_persistent_cache  # noqa: E402

configure_persistent_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sets", type=int, nargs="+", default=[128, 4096])
    ap.add_argument("--keys", type=int, default=32)
    ap.add_argument("--out", default=os.path.join(HERE, ".perf", "big_buckets.json"))
    args = ap.parse_args()

    from __graft_entry__ import _build_example
    from lighthouse_tpu.ops.pairing import fe_is_one
    from lighthouse_tpu.ops.verify import _device_verify

    results = []
    for n in args.sets:
        rec: dict = {"n_sets": n, "n_keys": args.keys, "platform": "cpu"}
        t0 = time.perf_counter()
        batch = _build_example(n_sets=n, n_keys=args.keys, seed=3)
        rec["build_secs"] = round(time.perf_counter() - t0, 1)

        t0 = time.perf_counter()
        lowered = jax.jit(_device_verify).lower(*batch)
        compiled = lowered.compile()
        rec["compile_secs"] = round(time.perf_counter() - t0, 1)

        t0 = time.perf_counter()
        fe, w_z = compiled(*batch)
        jax.block_until_ready((fe, w_z))
        exec_secs = time.perf_counter() - t0
        rec["exec_secs"] = round(exec_secs, 1)
        rec["sets_per_sec"] = round(n / exec_secs, 3)
        rec["verifies"] = bool(fe_is_one(fe))
        assert rec["verifies"], f"bucket {n}x{args.keys} failed to verify"
        results.append(rec)
        print(json.dumps(rec), flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(json.dumps({"buckets": results}) + "\n")


if __name__ == "__main__":
    main()
