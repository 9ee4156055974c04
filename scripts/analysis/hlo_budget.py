#!/usr/bin/env python
"""StableHLO budget auditor: per-(op, bucket) lowering locks (ISSUE 10).

Promotes ``tests/test_hlo_audit.py``'s ad-hoc dot-count assertions into a
committed, regenerable budget file — the lowering-level complement of the
AST passes in ``check_static.py``.  For every audited (op, backend, bucket)
this script lowers the program to StableHLO (trace only, no XLA compile)
and compares against ``scripts/analysis/hlo_budget_baseline.json``:

- ``dot_general``  — contraction dots (the MXU work; a rematerialized
  convolution or de-widened fused round shows up here first);
- ``s8_dot``       — dots whose operands are s8 (the int8 backend's MXU
  lock: every fq_mul pipeline must keep its s8 conv dot);
- ``convert``      — element-type conversions (an accidental dtype bounce
  inflates this long before it shows on a bench);
- ``transpose``    — layout shuffles (a batch-axis permutation sneaking
  into a lowering is a sharding hazard *and* a copy);
- ``collective``   — all_reduce/all_gather/etc.  Non-zero ONLY for the
  ``|dp8`` sharded keys (the mesh lowerings of bls_verify/kzg_batch — the
  batch-wide MSM / blob-axis lincombs complete through psums); every
  unsharded (``|-``) key stays locked at zero.  GSPMD inserts the
  collectives during partitioning, NOT in the traced StableHLO, so mesh
  targets count this one metric from the COMPILED module
  (``.lower(...).compile().as_text()`` — the persistent compile cache
  makes re-audits a deserialize); their other metrics still come from the
  pre-partitioning StableHLO, comparable with the unsharded keys.

Budget keys are ``op|backend|bucket|mesh`` — ``mesh`` is ``-`` for the
single-device lowering and ``dpN`` for the N-way mesh-sharded one
(in/out shardings derived from ``ops/batch_axes.py`` via
``device_mesh.ShardedEntry``, exactly as production derives them).  Mesh
targets need ``N`` jax devices to lower; below that the auditor SKIPS them
(reported, not failed) and ``--update-baseline`` keeps their committed
budgets — the full audit runs in the test suite's 8-device virtual CPU
mesh (``tests/test_hlo_audit.py``).

Unlike the AST passes this needs jax + lighthouse_tpu, so it runs from the
test suite (``tests/test_hlo_audit.py`` gates the small tier in tier-1, the
full set behind the ``slow`` marker), not from ``check_static.py`` — which
must stay import-free.

Workflow (same churn discipline as check_static):

    python scripts/analysis/hlo_budget.py                # self-test + audit
    python scripts/analysis/hlo_budget.py --tier all     # + slow buckets
    python scripts/analysis/hlo_budget.py --update-baseline [--tier all]

A deliberate lowering change (widening a contraction, a new bucket) is
re-baselined with ``--update-baseline`` and the diff reviewed like any
other; an unexplained budget drift fails CI.  All programs are lowered
through FRESH closures (jax's trace cache keys on callable identity — a
direct ``jax.jit(module_fn)`` could replay a trace made under the other
fq backend) over abstract ``ShapeDtypeStruct`` args (no data, no device).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Callable, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

BASELINE_PATH = os.path.join(
    REPO_ROOT, "scripts", "analysis", "hlo_budget_baseline.json"
)

METRICS = ("dot_general", "s8_dot", "convert", "transpose", "collective")

#: ops whose abstract args carry 64-bit dtypes — their traces must run
#: under ``enable_x64`` exactly as the production dispatches do
X64_OPS = frozenset({
    "epoch_deltas", "epoch_deltas_leak",
    "epoch_boundary", "epoch_boundary_leak",
    "proposer_select",
})

_COLLECTIVE_RE = re.compile(
    r"\b(all_reduce|all_gather|all_to_all|reduce_scatter|collective_permute"
    r"|collective_broadcast)\b"
)

#: Compiled (post-GSPMD) HLO spells collectives hyphenated.
_COMPILED_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast)\b"
)


# ----------------------------------------------------------------- counting


def count_budget(stablehlo_text: str) -> Dict[str, int]:
    """The budget metrics of one lowered module.  The int32 einsum lowers
    its elementwise outer product as a degenerate dot_general with
    ``contracting_dims = [] x []`` that XLA fuses into a multiply — only
    dots that actually contract count (same rule as the old test)."""
    dots = [
        l for l in stablehlo_text.splitlines()
        if "dot_general" in l and "contracting_dims = [] x []" not in l
    ]
    return {
        "dot_general": len(dots),
        "s8_dot": sum(1 for l in dots if l.count("xi8>") >= 2),
        "convert": stablehlo_text.count("stablehlo.convert"),
        "transpose": stablehlo_text.count("stablehlo.transpose"),
        "collective": len(_COLLECTIVE_RE.findall(stablehlo_text)),
    }


# ------------------------------------------------------------------ targets


class Target:
    """One audited (op, backend, bucket, mesh): ``build()`` returns
    ``(fresh_callable, abstract_args)`` ready for ``jax.jit(...).lower``.
    ``mesh_size`` > 0 lowers through the registry-derived mesh shardings
    (``entry_key`` names the ops/batch_axes.py declaration)."""

    def __init__(self, op: str, backend: str, bucket: str, tier: str,
                 build: Callable[[], Tuple[Callable, tuple]],
                 mesh_size: int = 0, entry_key: Optional[str] = None):
        self.op = op
        self.backend = backend  # "int32" | "int8" | "-" (fq-independent)
        self.bucket = bucket
        self.tier = tier        # "small" (tier-1) | "slow"
        self.build = build
        self.mesh_size = mesh_size
        self.entry_key = entry_key

    @property
    def mesh(self) -> str:
        return f"dp{self.mesh_size}" if self.mesh_size else "-"

    @property
    def key(self) -> str:
        return f"{self.op}|{self.backend}|{self.bucket}|{self.mesh}"


def _targets() -> List[Target]:
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import (  # noqa: F401 — lazily used below
        ec,
        epoch_device,
        kzg_device,
        pairing,
        sha256_device,
        shuffle_device,
        tower,
        tree_hash,
        verify,
    )

    S = jax.ShapeDtypeStruct
    i32 = jnp.int32

    def unwrap(f):
        # A module-level @jax.jit entry point caches ITS inner trace even
        # when lowered through a fresh outer closure — an int8 audit could
        # silently replay the int32 trace.  Lower the wrapped function.
        return getattr(f, "__wrapped__", f)

    a2 = S((4, 2, 25), i32)
    a12 = S((4, 2, 3, 2, 25), i32)
    g1 = tuple(S((4, 25), i32) for _ in range(3))
    g2 = tuple(S((4, 2, 25), i32) for _ in range(3))

    #: the tower/group-law primitives the old test locked (probe batch of 4)
    primitives = (
        ("fq2_mul", lambda: ((lambda a, b: tower.fq2_mul(a, b)), (a2, a2))),
        ("fq12_mul", lambda: ((lambda a, b: tower.fq12_mul(a, b)), (a12, a12))),
        ("fq12_square", lambda: ((lambda a: tower.fq12_square(a)), (a12,))),
        ("g1_point_add",
         lambda: ((lambda p, q: ec.point_add(ec.G1_OPS, p, q)), (g1, g1))),
        ("g1_point_double",
         lambda: ((lambda p: ec.point_double(ec.G1_OPS, p)), (g1,))),
        ("g2_proj_dbl",
         lambda: ((lambda t: pairing._proj_dbl(t)), (g2,))),
        ("g2_proj_add_mixed",
         lambda: ((lambda t, q: pairing._proj_add_mixed(t, q)),
                  (g2, (g2[0], g2[1])))),
    )

    def bls_build(nb: int, kb: int):
        def build():
            pk = tuple(S((nb, kb, 25), i32) for _ in range(3))
            sig = tuple(S((nb, 2, 25), i32) for _ in range(3))
            msg = tuple(S((nb, 2, 25), i32) for _ in range(2))
            return (
                (lambda *a: unwrap(verify._device_verify)(*a)),
                (pk, sig, msg, S((nb, 64), i32), S((nb,), jnp.bool_)),
            )
        return build

    def kzg_build(nb: int):
        def build():
            c = tuple(S((nb, 25), i32) for _ in range(3))
            p = tuple(S((nb, 25), i32) for _ in range(3))
            tau = tuple(S((2, 25), i32) for _ in range(2))
            g2g = tuple(S((2, 25), i32) for _ in range(2))
            return (
                (lambda *a: unwrap(kzg_device._device_kzg_batch)(*a)),
                (c, p, S((nb, 256), i32), S((nb, 256), i32),
                 S((256,), i32), tau, g2g),
            )
        return build

    def sha_build(nb: int):
        def build():
            return (
                (lambda w: unwrap(sha256_device._sha256_64byte_batch)(w)),
                (S((nb, 16), jnp.uint32),),
            )
        return build

    def epoch_build(n: int, in_leak: bool):
        def build():
            i64 = jnp.int64
            args = (
                [S((n,), i64)] * 4 + [S((n,), jnp.bool_)] + [S((n,), i64)] * 2
                + [S((), i64)] * 7
            )
            return (
                (lambda *a: unwrap(epoch_device._deltas_kernel)(
                    *a, in_leak=in_leak)),
                tuple(args),
            )
        return build

    def shuffle_build(n: int):
        def build():
            r = 90
            chunks = max(1, (n + 255) // 256)
            return (
                (lambda *a: unwrap(shuffle_device._shuffle_kernel)(*a)),
                (S((n,), i32), S((r,), i32),
                 S((r, chunks * 32), jnp.uint8), S((), i32)),
            )
        return build

    def proposer_build(n: int):
        def build():
            s, r = 32, 90
            k = shuffle_device.PROPOSER_CANDIDATES
            return (
                (lambda *a: unwrap(shuffle_device._proposer_kernel)(*a)),
                (S((s, 8), jnp.uint32), S((s, r), i32), S((s, k), i32),
                 S((n,), jnp.int64), S((), i32), S((), jnp.int64)),
            )
        return build

    def boundary_args(n: int):
        s, r = 32, 90
        k = shuffle_device.PROPOSER_CANDIDATES
        chunks = max(1, (n + 255) // 256)
        i64 = jnp.int64
        return tuple(
            [S((n,), i64)] * 4 + [S((n,), jnp.bool_)] + [S((n,), i64)] * 5
            + [S((n,), i32)]
            + [S((r,), i32), S((r, chunks * 32), jnp.uint8),
               S((s, 8), jnp.uint32), S((s, r), i32), S((s, k), i32)]
            + [S((), i64)] * 16 + [S((), i32)]
        )

    def boundary_build(n: int, in_leak: bool):
        def build():
            return (
                (lambda *a: unwrap(shuffle_device._boundary_kernel)(
                    *a, in_leak=in_leak)),
                boundary_args(n),
            )
        return build

    def boundary_mesh_build(n: int):
        def build():
            import functools

            # signature-preserving partial: ShardedEntry derives the
            # per-parameter shardings from the positional params (the
            # keyword-only static ``in_leak`` is bound, not scanned)
            return (
                functools.partial(unwrap(shuffle_device._boundary_kernel),
                                  in_leak=False),
                boundary_args(n),
            )
        return build

    out: List[Target] = []
    for backend in ("int32", "int8"):
        for name, build in primitives:
            out.append(Target(name, backend, "probe4", "small", build))
        out.append(Target("bls_verify", backend, "1x1", "small",
                          bls_build(1, 1)))
        out.append(Target("bls_verify", backend, "128x32", "slow",
                          bls_build(128, 32)))
        out.append(Target("kzg_batch", backend, "1", "small", kzg_build(1)))
        out.append(Target("kzg_batch", backend, "128", "slow", kzg_build(128)))
    def tree_build(m: int):
        def build():
            return (
                (lambda l: unwrap(tree_hash._tree_hash_subtrees)(l)),
                (S((m, 32, 8), jnp.uint32),),
            )
        return build

    out.append(Target("sha256_pairs", "-", "256", "small", sha_build(256)))
    # 640: the midpoint bucket the autotune controller (ISSUE 15) may
    # adopt between 256 and 1024 — adoption is REFUSED unless this key is
    # committed, so the budget is the adoption license.  Trace-only like
    # every unsharded key; cheap enough for tier-1.
    out.append(Target("sha256_pairs", "-", "640", "small", sha_build(640)))
    out.append(Target("sha256_pairs", "-", "4096", "slow", sha_build(4096)))
    # tree_hash: the fused depth-5 Merkle subtree program (ISSUE 13) —
    # small bucket in tier-1, the 2^20-leaf level's bucket behind slow.
    out.append(Target("tree_hash", "-", "8", "small", tree_build(8)))
    out.append(Target("tree_hash", "-", "32768", "slow", tree_build(32768)))
    for in_leak in (False, True):
        op = "epoch_deltas_leak" if in_leak else "epoch_deltas"
        out.append(Target(op, "-", "64", "small", epoch_build(64, in_leak)))
        out.append(Target(op, "-", "1024", "slow", epoch_build(1024, in_leak)))
        # the mainnet registry bucket (2^20 validators): trace-only like
        # every unsharded key, but big — slow tier
        out.append(Target(op, "-", "1048576", "slow",
                          epoch_build(1048576, in_leak)))
    # The fused epoch-boundary family (ISSUE 16): shuffle + proposer as
    # standalone entries, and the fused kernel in both leak modes — small
    # buckets in tier-1, the mainnet registry bucket behind slow.
    out.append(Target("shuffle", "-", "64", "small", shuffle_build(64)))
    out.append(Target("shuffle", "-", "1048576", "slow",
                      shuffle_build(1048576)))
    out.append(Target("proposer_select", "-", "64", "small",
                      proposer_build(64)))
    out.append(Target("proposer_select", "-", "1048576", "slow",
                      proposer_build(1048576)))
    for in_leak in (False, True):
        op = "epoch_boundary_leak" if in_leak else "epoch_boundary"
        out.append(Target(op, "-", "64", "small",
                          boundary_build(64, in_leak)))
        out.append(Target(op, "-", "1048576", "slow",
                          boundary_build(1048576, in_leak)))
    # Mesh-sharded lowerings (device_mesh.py): the batch axis of the full
    # entry points over the 8-way dp mesh.  These are the keys whose
    # ``collective`` budget is NON-zero — the bls batch-wide MSM and the
    # kzg blob-axis lincombs complete through psums.
    def bls_mesh_build(nb: int, kb: int):
        def build():
            # the UNWRAPPED fn itself (not a *args lambda): ShardedEntry
            # derives the per-parameter shardings from its signature
            pk = tuple(S((nb, kb, 25), i32) for _ in range(3))
            sig = tuple(S((nb, 2, 25), i32) for _ in range(3))
            msg = tuple(S((nb, 2, 25), i32) for _ in range(2))
            return (
                unwrap(verify._device_verify),
                (pk, sig, msg, S((nb, 64), i32), S((nb,), jnp.bool_), None),
            )
        return build

    def kzg_mesh_build(nb: int):
        def build():
            c = tuple(S((nb, 25), i32) for _ in range(3))
            p = tuple(S((nb, 25), i32) for _ in range(3))
            tau = tuple(S((2, 25), i32) for _ in range(2))
            g2g = tuple(S((2, 25), i32) for _ in range(2))
            return (
                unwrap(kzg_device._device_kzg_batch),
                (c, p, S((nb, 256), i32), S((nb, 256), i32),
                 S((256,), i32), tau, g2g),
            )
        return build

    # Tier split: the collective count needs a real (cacheable) compile —
    # one bls mesh key carries the tier-1 psum lock; the int8 twin and the
    # kzg mesh keys audit behind `slow` (cold compiles are ~80 s each on
    # the 1-core gate box; warm persistent cache makes them a deserialize).
    for backend, tier in (("int32", "small"), ("int8", "slow")):
        out.append(Target(
            "bls_verify", backend, "8x2", tier, bls_mesh_build(8, 2),
            mesh_size=8,
            entry_key="lighthouse_tpu/ops/verify.py:_device_verify"))
        out.append(Target(
            "kzg_batch", backend, "8", "slow", kzg_mesh_build(8),
            mesh_size=8,
            entry_key="lighthouse_tpu/ops/kzg_device.py:_device_kzg_batch"))
    # The fused boundary's 8-way lowering: its deltas sums complete
    # through psums and its mixed out_batched list replicates the
    # proposer table — the collective budget locks both.  Cold compile
    # is heavy, so slow tier (the 8-device test mesh audits it).
    out.append(Target(
        "epoch_boundary", "-", "64", "slow", boundary_mesh_build(64),
        mesh_size=8,
        entry_key="lighthouse_tpu/ops/shuffle_device.py:_boundary_kernel"))
    return out


def mesh_devices_available() -> int:
    import jax

    return len(jax.devices())


def _mesh_jit(target: Target, fn):
    """A jit wrapper carrying the registry-derived mesh shardings — the
    SAME derivation production uses (device_mesh.ShardedEntry)."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from lighthouse_tpu import device_mesh

    mesh = Mesh(np.array(jax.devices()[: target.mesh_size]),
                (device_mesh.AXIS,))
    entry = device_mesh.ShardedEntry(target.entry_key, fn)
    # the spec derivation needs fn's real signature (above), but the jit
    # must wrap a FRESH closure — jax's trace cache keys on callable
    # identity, and the raw module fn would replay the other backend's
    # trace (the same discipline as the unsharded targets' lambdas)
    return jax.jit(lambda *a: fn(*a),
                   in_shardings=entry.in_shardings(mesh),
                   out_shardings=entry.out_sharding(mesh))


def measure_target(target: Target) -> Dict[str, int]:
    """The budget metrics of one target.  Unsharded: counted from the
    traced StableHLO (trace only, no XLA compile).  Mesh: the
    ``collective`` metric comes from the COMPILED module — GSPMD inserts
    the collectives during partitioning, so the traced text carries only
    sharding annotations (the remaining metrics still count the traced
    text, comparable with the unsharded keys)."""
    import jax

    from lighthouse_tpu.ops import fq

    fn, args = target.build()
    if target.backend in ("int32", "int8"):
        prev = fq.set_fq_backend(target.backend)
    else:
        prev = fq.set_fq_backend("int32")  # fq-independent: pin for determinism
    try:
        jitted = _mesh_jit(target, fn) if target.mesh_size else jax.jit(fn)

        def measure():
            lowered = jitted.lower(*args)
            counts = count_budget(lowered.as_text())
            if target.mesh_size:
                counts["collective"] = len(_COMPILED_COLLECTIVE_RE.findall(
                    lowered.compile().as_text()))
            return counts

        if target.op in X64_OPS:
            with jax.enable_x64(True):
                return measure()
        return measure()
    finally:
        fq.set_fq_backend(prev)


# ----------------------------------------------------------------- baseline


def serialize_budgets(budgets: Dict[str, Dict[str, int]]) -> str:
    """Canonical byte form: sorted keys, 2-space indent, trailing newline —
    ``--update-baseline`` must round-trip byte-identically."""
    return json.dumps(budgets, indent=2, sort_keys=True) + "\n"


def load_baseline() -> Dict[str, Dict[str, int]]:
    if not os.path.exists(BASELINE_PATH):
        return {}
    with open(BASELINE_PATH, "r", encoding="utf-8") as f:
        return json.load(f)


def write_baseline(budgets: Dict[str, Dict[str, int]]) -> None:
    with open(BASELINE_PATH, "w", encoding="utf-8") as f:
        f.write(serialize_budgets(budgets))


# -------------------------------------------------------------------- audit


def compare(key: str, want: Optional[Dict[str, int]],
            got: Dict[str, int]) -> List[str]:
    """Human-readable mismatches for one target (empty == within budget)."""
    if want is None:
        return [f"{key}: no committed budget — run --update-baseline and "
                "review the diff"]
    out = []
    for metric in METRICS:
        w, g = want.get(metric), got.get(metric, 0)
        if w != g:
            out.append(f"{key}: {metric} budget {w}, lowered {g}")
    return out


def audit(tier: str = "small", verbose: bool = False,
          ) -> Tuple[List[str], Dict[str, Dict[str, int]]]:
    """(mismatches, measured budgets) for every target in ``tier``
    ("small" = tier-1 set, "all" = small + slow).  Baseline keys that no
    target declares anymore are mismatches too (a renamed/removed target
    must not leave an orphan budget reading as audited coverage — the
    budget-file analog of the sharding pass's registry-stale).  Mesh
    targets are SKIPPED (not failed) when the interpreter has fewer
    devices than their mesh — the full audit needs the test suite's
    8-device virtual CPU mesh."""
    baseline = load_baseline()
    mismatches: List[str] = []
    measured: Dict[str, Dict[str, int]] = {}
    targets = _targets()
    declared = {t.key for t in targets}
    for key in sorted(set(baseline) - declared):
        mismatches.append(
            f"{key}: stale budget entry — no such audit target; "
            "run --update-baseline (it prunes undeclared keys)"
        )
    n_devices = mesh_devices_available()
    skipped = 0
    for target in targets:
        if tier != "all" and target.tier != "small":
            continue
        if target.mesh_size > n_devices:
            skipped += 1
            continue
        got = measure_target(target)
        measured[target.key] = got
        mismatches.extend(compare(target.key, baseline.get(target.key), got))
        if verbose:
            print(f"hlo_budget: {target.key}: {got}")
    if skipped:
        print(f"hlo_budget: skipped {skipped} mesh target(s) — "
              f"{n_devices} device(s) here; run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "for the full audit", file=sys.stderr)
    return mismatches, measured


def self_test() -> List[str]:
    """The auditor must still be able to SEE (a blind budget check passes
    everything): count a known program, detect the s8 lock, and detect a
    seeded budget perturbation."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    errors: List[str] = []
    f32 = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    txt = jax.jit(lambda a, b: a @ b).lower(f32, f32).as_text()
    counts = count_budget(txt)
    if counts["dot_general"] != 1:
        errors.append(
            f"self-test: matmul counted {counts['dot_general']} contraction "
            "dots, expected 1 — the dot counter has gone blind"
        )
    i8 = jax.ShapeDtypeStruct((8, 8), jnp.int8)
    txt8 = jax.jit(
        lambda a, b: jax.lax.dot(a, b, preferred_element_type=jnp.int32)
    ).lower(i8, i8).as_text()
    if count_budget(txt8)["s8_dot"] != 1:
        errors.append(
            "self-test: s8 matmul not counted as s8_dot — the s8-operand "
            "lock has gone blind"
        )
    perturbed = dict(counts)
    perturbed["dot_general"] += 1
    if not compare("self|test|probe", perturbed, counts):
        errors.append(
            "self-test: a seeded budget perturbation was not detected — "
            "the comparator has gone blind"
        )
    if len(jax.devices()) >= 2:
        # The collective counter must SEE a psum: a batch-axis sum sharded
        # over two devices partitions into an all-reduce by construction
        # (GSPMD inserts it at compile time — count the compiled module).
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        txt_c = jax.jit(
            lambda x: x.sum(axis=0),
            in_shardings=NamedSharding(mesh, P("dp")),
            out_shardings=NamedSharding(mesh, P()),
        ).lower(jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile().as_text()
        if len(_COMPILED_COLLECTIVE_RE.findall(txt_c)) < 1:
            errors.append(
                "self-test: a sharded batch-axis sum compiled with no "
                "counted collective — the psum lock has gone blind"
            )
    return errors


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tier", choices=("small", "all"), default="small",
                    help="small = tier-1 buckets; all = + slow buckets")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the audited keys' budgets from the tree")
    ap.add_argument("--no-self-test", action="store_true")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()

    errors: List[str] = []
    if not args.no_self_test:
        errors.extend(self_test())

    if args.update_baseline:
        if errors:
            # A blind counter must never be committed as the new budget.
            for e in errors:
                print(f"hlo_budget: FAIL: {e}", file=sys.stderr)
            print("hlo_budget: refusing to rewrite the baseline with a "
                  "failing self-test", file=sys.stderr)
            return 1
        _, measured = audit(args.tier, args.verbose)
        budgets = load_baseline()
        budgets.update(measured)
        declared = {t.key for t in _targets()}
        stale = sorted(set(budgets) - declared)
        for key in stale:
            del budgets[key]
        write_baseline(budgets)
        pruned = f", pruned {len(stale)} stale" if stale else ""
        print(f"hlo_budget: baseline rewritten for {len(measured)} "
              f"target(s) (tier={args.tier}{pruned})")
        return 0

    mismatches, measured = audit(args.tier, args.verbose)
    for m in mismatches:
        print(f"hlo_budget: FAIL: {m}", file=sys.stderr)
    for e in errors:
        print(f"hlo_budget: FAIL: {e}", file=sys.stderr)
    if mismatches or errors:
        print(
            f"hlo_budget: {len(mismatches)} budget mismatch(es), "
            f"{len(errors)} self-test failure(s). Deliberate lowering "
            "changes: --update-baseline and review the diff (ANALYSIS.md).",
            file=sys.stderr,
        )
        return 1
    print(
        f"hlo_budget: OK ({len(measured)} (op, bucket) budgets within "
        f"baseline, tier={args.tier}, self-test "
        f"{'skipped' if args.no_self_test else 'fired'})"
    )
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    sys.exit(main())
