#!/usr/bin/env python
"""Occupancy-driven bucket-vocabulary tuning report (ISSUE 13 satellite).

The device telemetry layer already accounts padding waste per dispatched
batch (``device_batch_occupancy_ratio{op,axis}`` + the flight recorder's
per-batch ``occupancy_sets``/``occupancy_keys``), and ROADMAP item 2 names
occupancy-driven bucket tuning as a self-tuning slice.  This script is the
report-only half: it reads a captured telemetry summary — the JSON body of
``GET /lighthouse/device``, or a BENCH JSON artifact carrying a
``device_telemetry`` section — and prints suggested deltas for the three
bucket vocabularies:

- ``ops/verify.N_BUCKETS`` / ``K_BUCKETS``   (bls sets / keys-per-set)
- ``ops/sha256_device.N_BUCKETS``            (pair-hash blocks)
- ``ops/epoch_device.N_BUCKETS``             (registry buckets)
- ``ops/tree_hash.N_BUCKETS``                (Merkle subtrees)

Heuristics (documented so the report is reviewable, not oracular):

- p50 occupancy below ``DENSIFY_BELOW`` → the vocabulary is too sparse
  around the observed live sizes: suggest inserting the midpoint bucket
  between the two surrounding powers of two (occupancy can then never drop
  below ~50% at that size).
- p90 occupancy above ``WIDEN_ABOVE`` with the top bucket saturated →
  traffic is pressing the ceiling: suggest the next power of two.
- too few samples → say so and suggest nothing (a tuning change must rest
  on evidence, ``MIN_SAMPLES`` batches per op/axis).

This SCRIPT stays report-only (it changes no behavior and writes no
files); the same heuristics run live inside the node via
``lighthouse_tpu/autotune.py`` (ISSUE 15), where adoptions are guarded by
the committed hlo_budget baseline and off-path AOT warmup.  The
vocabularies are read LIVE from the ``ops/batch_axes.py``-registered
modules so suggestions cannot go stale against the sources; the committed
fallback snapshot only serves bare-dump triage outside the repo.

Usage::

    python scripts/analysis/bucket_tuning.py --from-json device_summary.json
    curl -s localhost:5052/lighthouse/device | \
        python scripts/analysis/bucket_tuning.py --from-json -

Import-free of lighthouse_tpu/jax (runs anywhere, same discipline as
check_static); the vocabularies above are quoted as literals and
self-tested against seeded fixtures on every run.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

#: Fallback snapshot for running on a bare telemetry dump OUTSIDE the repo
#: (laptop triage of a prod JSON).  Inside the repo the vocabularies are
#: READ LIVE from the ``ops/batch_axes.py``-registered modules — these
#: literals are never consulted when the sources are present, and the
#: self-test fails if they drift from the live values (a stale snapshot
#: must not silently mis-advise an offline triage).
FALLBACK_VOCABULARIES: Dict[str, List[int]] = {
    "bls_verify/sets": [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                        4096],
    "bls_verify/keys": [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048],
    "sha256_pairs/sets": [256, 1024, 4096, 16384, 65536, 262144],
    "epoch_deltas/sets": [64, 256, 1024, 4096, 16384, 65536, 262144,
                          1048576],
    "tree_hash/sets": [8, 128, 2048, 32768],
    "kzg_batch/sets": [1, 2, 4, 8, 16, 32, 64, 128, 256, 512],
}

#: op/axis (as telemetry spells them) -> vocabulary key
AXIS_TO_VOCAB = {
    ("bls_verify", "sets"): "bls_verify/sets",
    ("bls_verify", "keys"): "bls_verify/keys",
    ("sha256_pairs", "sets"): "sha256_pairs/sets",
    ("epoch_deltas", "sets"): "epoch_deltas/sets",
    ("epoch_deltas_leak", "sets"): "epoch_deltas/sets",
    ("tree_hash", "sets"): "tree_hash/sets",
    ("kzg_batch", "sets"): "kzg_batch/sets",
}

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
_BATCH_AXES_PATH = os.path.join(_ROOT, "lighthouse_tpu", "ops",
                                "batch_axes.py")


def _literal_vocab(text: str, name: str) -> Optional[List[int]]:
    m = re.search(rf"^{name}\s*=\s*\(([^)]*)\)", text, re.MULTILINE)
    if not m:
        return None
    vals = [int(v.strip()) for v in m.group(1).split(",") if v.strip()]
    return vals or None


def _registered_modules() -> Optional[Dict[str, str]]:
    """op name -> repo-relative module path, from the batch-axis registry
    (parsed with ast.literal_eval — this script stays import-free of
    lighthouse_tpu/jax, same discipline as the sharding pass).  None when
    the registry is absent (bare-dump mode)."""
    try:
        with open(_BATCH_AXES_PATH, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "BATCH_AXES"
                for t in node.targets):
            try:
                registry = ast.literal_eval(node.value)
            except ValueError:
                return None
            return {
                entry["op"]: key.split(":")[0]
                for key, entry in registry.items()
            }
    return None


def read_live_vocabularies() -> Tuple[Optional[Dict[str, List[int]]],
                                      List[str]]:
    """(vocabularies, errors) read LIVE from the registered modules'
    ``N_BUCKETS``/``K_BUCKETS`` literals — suggestions can never go stale
    against the sources.  ``(None, [])`` when the repo sources are absent
    (callers fall back to the committed snapshot); a registered op with no
    readable vocabulary is an ERROR."""
    modules = _registered_modules()
    if modules is None:
        return None, []
    vocabs: Dict[str, List[int]] = {}
    errors: List[str] = []
    for op, rel in sorted(modules.items()):
        path = os.path.join(_ROOT, rel)
        try:
            with open(path, "r", encoding="utf-8") as f:
                text = f.read()
        except OSError:
            errors.append(f"{op}: registered module {rel} unreadable")
            continue
        n_buckets = _literal_vocab(text, "N_BUCKETS")
        if n_buckets is None:
            errors.append(
                f"{op}: registered module {rel} declares no N_BUCKETS "
                "vocabulary — a device entry point must be tunable")
            continue
        # telemetry spells the leak-mode epoch op separately but both
        # share one registry vocabulary (AXIS_TO_VOCAB folds them)
        base = "epoch_deltas" if op.startswith("epoch_deltas") else op
        vocabs[f"{base}/sets"] = n_buckets
        k_buckets = _literal_vocab(text, "K_BUCKETS")
        if k_buckets is not None:
            vocabs[f"{base}/keys"] = k_buckets
    return vocabs, errors


_VOCAB_CACHE: Optional[Dict[str, List[int]]] = None


def get_vocabularies() -> Dict[str, List[int]]:
    """The vocabularies suggestions run against: live-read inside the
    repo, the committed fallback snapshot elsewhere."""
    global _VOCAB_CACHE
    if _VOCAB_CACHE is None:
        live, _ = read_live_vocabularies()
        _VOCAB_CACHE = live if live else dict(FALLBACK_VOCABULARIES)
    return _VOCAB_CACHE

DENSIFY_BELOW = 0.5   # p50 occupancy under this → suggest midpoint buckets
WIDEN_ABOVE = 0.98    # p90 at the top bucket over this → suggest next pow2
MIN_SAMPLES = 8


def _occupancy_sections(doc: dict) -> Optional[dict]:
    """The ``occupancy`` section from either a /lighthouse/device summary
    or a BENCH JSON artifact (``device_telemetry.occupancy``)."""
    if "occupancy" in doc:
        return doc["occupancy"]
    dt = doc.get("device_telemetry")
    if isinstance(dt, dict) and "occupancy" in dt:
        return dt["occupancy"]
    return None


def suggest(doc: dict) -> List[dict]:
    """The report rows: one dict per (op, axis) with evidence + suggestion."""
    occ = _occupancy_sections(doc)
    rows: List[dict] = []
    if not occ:
        return rows
    vocabularies = get_vocabularies()
    for op, axes in sorted(occ.items()):
        for axis, stats in sorted((axes or {}).items()):
            if not stats:
                continue
            vocab_key = AXIS_TO_VOCAB.get((op, axis))
            row = {
                "op": op,
                "axis": axis,
                "samples": stats.get("n", 0),
                "p50": stats.get("p50"),
                "p90": stats.get("p90"),
                "vocabulary": vocab_key,
                "suggestion": None,
                "reason": None,
            }
            rows.append(row)
            if vocab_key is None:
                row["reason"] = "no bucket vocabulary maps to this axis"
                continue
            if row["samples"] < MIN_SAMPLES:
                row["reason"] = (
                    f"only {row['samples']} batches in the window "
                    f"(need {MIN_SAMPLES}) — no suggestion on thin evidence")
                continue
            vocab = vocabularies.get(vocab_key)
            if not vocab:
                row["reason"] = (f"vocabulary {vocab_key} not readable from "
                                 "the registered sources")
                continue
            p50 = row["p50"] if row["p50"] is not None else 1.0
            p90 = row["p90"] if row["p90"] is not None else p50
            if p50 < DENSIFY_BELOW:
                # Padding-waste dominated: the median batch fills under half
                # its bucket, so the gap between adjacent buckets is too
                # wide around the live sizes.  Midpoints bound occupancy at
                # ~50% by construction.
                mids = sorted({
                    (vocab[i] + vocab[i + 1]) // 2
                    for i in range(len(vocab) - 1)
                    if vocab[i + 1] > 2 * vocab[i]  # only real gaps
                })
                if mids:
                    row["suggestion"] = {"insert_buckets": mids[:4]}
                    row["reason"] = (
                        f"p50 occupancy {p50:.2f} < {DENSIFY_BELOW}: the "
                        "median batch wastes over half its lanes — densify "
                        "the vocabulary with midpoint buckets")
                else:
                    # ratio-2 (pure power-of-two) vocabulary: occupancy
                    # can't drop below 50% from bucket gaps, so a low p50
                    # means tiny live batches — a traffic question (linger,
                    # coalescing target), not a vocabulary one
                    row["reason"] = (
                        f"p50 occupancy {p50:.2f} < {DENSIFY_BELOW} but the "
                        "vocabulary is already ratio-2 dense — no midpoint "
                        "exists; look at coalescing (linger/target), not "
                        "buckets")
            elif p90 >= WIDEN_ABOVE:
                row["suggestion"] = {"append_bucket": vocab[-1] * 2}
                row["reason"] = (
                    f"p90 occupancy {p90:.2f} >= {WIDEN_ABOVE}: traffic is "
                    "pressing the top bucket — consider the next power of "
                    "two (compile-cost review required)")
            else:
                row["reason"] = (
                    f"occupancy healthy (p50 {p50:.2f}, p90 {p90:.2f}) — "
                    "no change suggested")
    return rows


def render(rows: List[dict]) -> str:
    if not rows:
        return ("bucket_tuning: no occupancy data in the input — pass the "
                "JSON body of GET /lighthouse/device (or a BENCH artifact "
                "with a device_telemetry section)")
    lines = ["bucket_tuning: occupancy-driven bucket report (report-only; "
             "edit the named vocabulary and review the diff)"]
    for row in rows:
        head = (f"  {row['op']}/{row['axis']}: n={row['samples']} "
                f"p50={row['p50']} p90={row['p90']}")
        lines.append(head)
        lines.append(f"    -> {row['reason']}")
        if row["suggestion"]:
            lines.append(
                f"    -> suggest {json.dumps(row['suggestion'])} "
                f"in {row['vocabulary']}")
    return "\n".join(lines)


# ---------------------------------------------------------------- self-test


def self_test() -> List[str]:
    """Seeded fixtures: the heuristics must still see — a waste-heavy
    fixture must suggest densifying, a saturated one widening, a thin one
    nothing; and (when run from the repo) every batch_axes-registered op
    must yield a live vocabulary, with the fallback snapshot matching the
    live read."""
    errors: List[str] = []
    waste = {"occupancy": {"sha256_pairs": {
        "sets": {"n": 32, "p50": 0.12, "p90": 0.4}}}}
    rows = suggest(waste)
    if not rows or not rows[0]["suggestion"] or \
            rows[0]["suggestion"].get("insert_buckets", [None])[0] != 640:
        errors.append("waste fixture produced no densify suggestion")
    # a pure power-of-two vocabulary has no midpoints: low occupancy must
    # fall through to the "already dense" reason, never an empty suggestion
    pow2_waste = {"occupancy": {"bls_verify": {
        "sets": {"n": 32, "p50": 0.12, "p90": 0.4}}}}
    rows = suggest(pow2_waste)
    if not rows or rows[0]["suggestion"] is not None or \
            "ratio-2 dense" not in (rows[0]["reason"] or ""):
        errors.append("pow2 waste fixture should suggest nothing "
                      "(already ratio-2 dense)")
    full = {"occupancy": {"sha256_pairs": {
        "sets": {"n": 32, "p50": 0.99, "p90": 1.0}}}}
    rows = suggest(full)
    if not rows or not rows[0]["suggestion"] or \
            rows[0]["suggestion"].get("append_bucket") != 524288:
        errors.append("saturated fixture produced no widen suggestion")
    thin = {"occupancy": {"bls_verify": {
        "sets": {"n": 2, "p50": 0.1, "p90": 0.1}}}}
    rows = suggest(thin)
    if not rows or rows[0]["suggestion"] is not None:
        errors.append("thin-evidence fixture still suggested a change")
    bench_shape = {"device_telemetry": {"occupancy": {"bls_verify": {
        "sets": {"n": 32, "p50": 0.9, "p90": 0.95}}}}}
    if not suggest(bench_shape):
        errors.append("BENCH-shaped input (device_telemetry section) unread")
    errors.extend(_check_live_vocabularies())
    return errors


def _check_live_vocabularies() -> List[str]:
    """Inside the repo: every batch_axes-registered op must yield a live
    vocabulary (the read_live_vocabularies errors ARE self-test failures —
    a registered device entry point with nothing to tune is either a
    missing N_BUCKETS), and the offline fallback
    snapshot must match the live read.  Silently skipped on a bare
    telemetry dump outside the repo."""
    live, read_errors = read_live_vocabularies()
    if live is None:
        return []
    errors = list(read_errors)
    for key, snapshot in FALLBACK_VOCABULARIES.items():
        got = live.get(key)
        if got is not None and got != snapshot:
            errors.append(
                f"{key}: fallback snapshot {snapshot} != live source {got} "
                "— update FALLBACK_VOCABULARIES (offline triage would "
                "mis-advise)")
    for key in live:
        if key not in FALLBACK_VOCABULARIES:
            errors.append(
                f"{key}: live vocabulary has no fallback snapshot — add it "
                "to FALLBACK_VOCABULARIES")
    errors.extend(_check_runtime_thresholds())
    return errors


def _check_runtime_thresholds() -> List[str]:
    """The runtime controller (lighthouse_tpu/autotune.py) runs these same
    densify heuristics live — a threshold edited on one side silently
    diverges report from runtime, so the literals are drift-checked (text
    scan, import-free; skipped outside the repo)."""
    path = os.path.join(_ROOT, "lighthouse_tpu", "autotune.py")
    try:
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return []
    errors: List[str] = []
    for name, here in (("DENSIFY_BELOW", DENSIFY_BELOW),
                       ("MIN_SAMPLES", MIN_SAMPLES)):
        m = re.search(rf"^{name}\s*=\s*([0-9.]+)", text, re.MULTILINE)
        if not m:
            errors.append(f"autotune.py: no {name} literal found — the "
                          "runtime/report heuristic pairing broke")
        elif float(m.group(1)) != float(here):
            errors.append(
                f"{name}: report {here} != runtime {m.group(1)} in "
                "lighthouse_tpu/autotune.py — the offline report would "
                "suggest buckets the live controller disagrees about")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--from-json", dest="src", default=None,
                    help="path to a GET /lighthouse/device body or BENCH "
                         "JSON artifact ('-' = stdin)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report rows as JSON instead of text")
    ap.add_argument("--no-self-test", action="store_true")
    args = ap.parse_args()

    if not args.no_self_test:
        errors = self_test()
        if errors:
            for e in errors:
                print(f"bucket_tuning: FAIL: {e}", file=sys.stderr)
            return 1

    if args.src is None:
        print("bucket_tuning: self-test OK (pass --from-json to analyze a "
              "telemetry dump)")
        return 0

    raw = sys.stdin.read() if args.src == "-" else open(args.src).read()
    rows = suggest(json.loads(raw))
    if args.json:
        print(json.dumps(rows, indent=2, sort_keys=True))
    else:
        print(render(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
