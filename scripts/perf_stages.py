"""Stage-split CPU-jax timing + HLO cost audit for the fused BLS verifier.

VERDICT r4 item 2: break ``ops.verify._device_verify`` into its jittable
stages, time each on CPU-jax at 16 and 128 sets, dump per-stage
``cost_analysis()`` FLOP counts, and prove the ``fq_mul`` convolution
einsum lowers to exactly ONE dot per multiply pipeline (not rematerialized).

Reference semantics being profiled: the batch-verification equation of
``/root/reference/crypto/bls/src/impls/blst.rs:35-117`` — per-set pubkey
aggregation, G1/G2 random-weight scalar muls, Miller loop, final exp.

Usage:
    python scripts/perf_stages.py --sets 16 --out .perf/stages_16.json
    python scripts/perf_stages.py --sets 128 --reps 1 --out .perf/stages_128.json

Writes one JSON file per run; PERF.md aggregates the committed results.
"""

from __future__ import annotations

import argparse
import json
import os
import re
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

# JAX_COMPILATION_CACHE_DIR if set, else <repo>/.jax_cache: the perf
# harness shares the node's cache.
configure_persistent_cache()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from __graft_entry__ import _build_example  # noqa: E402
from lighthouse_tpu.ops import ec, fq as fq_mod, pairing, tower  # noqa: E402
from lighthouse_tpu.ops.verify import _NEG_G1, _device_verify  # noqa: E402
from lighthouse_tpu.ops.pairing import fe_is_one  # noqa: E402


# --------------------------------------------------------------------- stages


@jax.jit
def s1_g1_weighted(pk, wbits):
    """Per-set pubkey tree-sum + G1 windowed scalar-mul ([r_i] aggpk_i)."""
    agg = ec.tree_sum(ec.G1_OPS, pk, axis=1)
    return ec.scalar_mul_windowed(ec.G1_OPS, agg, wbits)


@jax.jit
def s2_g2_msm(sig, wbits):
    """W = sum_i [r_i] sig_i — one shared-window G2 MSM."""
    return ec.msm_windowed(ec.G2_OPS, sig, wbits)


@jax.jit
def s3_w_affine(w):
    """W -> affine (one fq2 inversion = 381-bit pow chain)."""
    zi = tower.fq2_inv(w[2])
    return (tower.fq2_mul(w[0], zi), tower.fq2_mul(w[1], zi))


@jax.jit
def s4_miller(p_weighted, w_aff, msg, live):
    """Assemble N+1 pairs and run the batched Miller loop."""
    def cat(a, b):
        return jnp.concatenate([a, b[None]], axis=0)

    p1 = tuple(cat(p_weighted[i], jnp.asarray(_NEG_G1[i])) for i in range(3))
    q2 = tuple(cat(msg[i], w_aff[i]) for i in range(2))
    mask = jnp.concatenate([live, jnp.asarray([True])])
    f = pairing.miller_loop(p1, q2)
    return jnp.where(mask.reshape(mask.shape + (1,) * 4), f, tower.FQ12_ONE)


@jax.jit
def s5_reduce_fe(f):
    """Product across pairs + shared final exponentiation."""
    n = f.shape[0]
    n2 = 1 << (n - 1).bit_length()
    if n2 != n:
        pad = jnp.broadcast_to(tower.FQ12_ONE, (n2 - n,) + f.shape[1:])
        f = jnp.concatenate([f, pad], axis=0)
    return pairing.final_exponentiation(pairing.fq12_product(f))


def _time_stage(fn, args, reps: int):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    return out, warm, dt


def _flops(fn, args) -> dict:
    try:
        an = fn.lower(*args).compile().cost_analysis()
        if isinstance(an, (list, tuple)):
            an = an[0]
        return {
            "flops": float(an.get("flops", -1.0)),
            "bytes_accessed": float(an.get("bytes accessed", -1.0)),
        }
    except Exception as e:
        return {"cost_analysis_error": f"{type(e).__name__}: {e}"}


def _count_dots(txt: str) -> int:
    return len(re.findall(r"\bdot\(", txt)) + len(re.findall(r"\bdot-general\b", txt))


def _count_s8_dots(stablehlo: str) -> int:
    """dot_generals in the LOWERED (pre-XLA) module whose operands are both
    i8 — the emission the int8 backend promises; what the platform compiler
    does afterwards is its own business."""
    n = 0
    for line in stablehlo.splitlines():
        if "dot_general" in line and line.count("xi8>") >= 2:
            n += 1
    return n


def _dot_audit() -> dict:
    """Count dot ops in the optimized HLO of the hot-path kernels.

    The design claims being locked in: (1) every tower multiply stacks its
    Karatsuba sub-products onto one axis and issues ONE fq_mul pipeline —
    one convolution einsum + one reduction einsum = exactly 2 dots,
    regardless of tower level (more would mean XLA rematerialized the
    contraction); (2) the widened group-law / Miller-step schedules fuse
    each round of independent products into one pipeline (point_add: 2
    pipelines = 4 dots, vs 24 for the per-mul schedule); (3) under the int8
    backend the convolution dots carry s8 operands (counted on the lowered
    StableHLO).
    """
    out = {"fq_backend": fq_mod.active_fq_backend()}
    a2 = jnp.asarray(np.ones((4, 2, 25), np.int32))
    a12 = jnp.asarray(np.ones((4, 2, 3, 2, 25), np.int32))
    g1 = tuple(jnp.asarray(np.stack([c] * 4)) for c in ec.G1_GEN_LIMBS)
    g2 = tuple(jnp.asarray(np.stack([c] * 4)) for c in ec.G2_GEN_LIMBS)
    g2_aff = (g2[0], g2[1])
    # Every target is wrapped in a FRESH lambda: jax's trace cache keys on
    # the wrapped callable's identity, so jitting a module-level function
    # directly could replay a trace made under the other fq backend.
    for name, fn, args in (
        ("fq2_mul", jax.jit(lambda a, b: tower.fq2_mul(a, b)), (a2, a2)),
        ("fq12_mul", jax.jit(lambda a, b: tower.fq12_mul(a, b)), (a12, a12)),
        ("fq12_square", jax.jit(lambda a: tower.fq12_square(a)), (a12,)),
        ("g1_point_add", jax.jit(lambda p, q: ec.point_add(ec.G1_OPS, p, q)),
         (g1, g1)),
        ("g1_point_double", jax.jit(lambda p: ec.point_double(ec.G1_OPS, p)),
         (g1,)),
        ("g2_proj_dbl", jax.jit(lambda t: pairing._proj_dbl(t)), (g2,)),
        ("g2_proj_add_mixed", jax.jit(lambda t, q: pairing._proj_add_mixed(t, q)),
         (g2, g2_aff)),
    ):
        try:
            lowered = fn.lower(*args)
            out[name + "_s8_dots"] = _count_s8_dots(lowered.as_text())
            out[name + "_dots"] = _count_dots(lowered.compile().as_text())
        except Exception as e:
            out[name + "_dots_error"] = f"{type(e).__name__}: {e}"
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sets", type=int, default=16)
    ap.add_argument("--keys", type=int, default=32)
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument("--out", default="")
    ap.add_argument("--skip-dot-audit", action="store_true")
    ap.add_argument("--device", action="store_true",
                    help="run on the live platform (TPU) instead of pinning CPU")
    ap.add_argument("--fq-backend", choices=("int8", "int32"), default=None,
                    help="force the fq_mul lowering (default: env/auto)")
    args = ap.parse_args()

    if args.fq_backend:
        fq_mod.set_fq_backend(args.fq_backend)
    n, k = args.sets, args.keys
    res: dict = {"n_sets": n, "n_keys": k, "reps": args.reps,
                 "platform": jax.devices()[0].platform,
                 "fq_backend": fq_mod.active_fq_backend()}

    t0 = time.perf_counter()
    pk, sig, msg, wbits, live, _ = _build_example(n_sets=n, n_keys=k, seed=3)
    res["build_batch_secs"] = round(time.perf_counter() - t0, 2)

    stages = []
    p_weighted, warm, dt = _time_stage(s1_g1_weighted, (pk, wbits), args.reps)
    stages.append({"stage": "s1_g1_agg+windowed_mul", "warm_secs": round(warm, 2),
                   "exec_secs": round(dt, 3), **_flops(s1_g1_weighted, (pk, wbits))})

    w, warm, dt = _time_stage(s2_g2_msm, (sig, wbits), args.reps)
    stages.append({"stage": "s2_g2_msm", "warm_secs": round(warm, 2),
                   "exec_secs": round(dt, 3), **_flops(s2_g2_msm, (sig, wbits))})

    w_aff, warm, dt = _time_stage(s3_w_affine, (w,), args.reps)
    stages.append({"stage": "s3_w_to_affine(fq2_inv)", "warm_secs": round(warm, 2),
                   "exec_secs": round(dt, 3), **_flops(s3_w_affine, (w,))})

    f, warm, dt = _time_stage(s4_miller, (p_weighted, w_aff, msg, live), args.reps)
    stages.append({"stage": "s4_miller_loop", "warm_secs": round(warm, 2),
                   "exec_secs": round(dt, 3),
                   **_flops(s4_miller, (p_weighted, w_aff, msg, live))})

    fe, warm, dt = _time_stage(s5_reduce_fe, (f,), args.reps)
    stages.append({"stage": "s5_product+final_exp", "warm_secs": round(warm, 2),
                   "exec_secs": round(dt, 3), **_flops(s5_reduce_fe, (f,))})

    res["stages"] = stages
    res["stage_exec_total_secs"] = round(sum(s["exec_secs"] for s in stages), 3)

    # Cross-check: staged result must verify, matching the fused program.
    res["staged_verifies"] = bool(fe_is_one(fe))

    # Fused end-to-end for the same batch (warm from .jax_cache if available).
    t0 = time.perf_counter()
    fe2, wz = _device_verify(pk, sig, msg, wbits, live)
    jax.block_until_ready((fe2, wz))
    res["fused_warm_secs"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()
    for _ in range(args.reps):
        fe2, wz = _device_verify(pk, sig, msg, wbits, live)
    jax.block_until_ready((fe2, wz))
    res["fused_exec_secs"] = round((time.perf_counter() - t0) / args.reps, 3)
    res["fused_sets_per_sec"] = round(n / res["fused_exec_secs"], 3)
    res["fused_verifies"] = bool(fe_is_one(fe2))

    if not args.skip_dot_audit:
        res["dot_audit"] = _dot_audit()

    line = json.dumps(res)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")


if __name__ == "__main__":
    main()
