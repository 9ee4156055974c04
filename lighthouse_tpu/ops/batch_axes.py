"""Batch-axis registry: the sharding contract of every device entry point.

ROADMAP item 1 (the mesh in the production dispatch path — landed: the
mesh layer behind ``ops/dispatch.py``) shards the *batch axis* of the bucketed
device programs over a ``jax.sharding.Mesh``.  That only works if the
batch axis is a real, declared property of each entry point — not folklore
living in docstrings.  This registry IS that declaration: one entry per
jitted device entry point in ``ops/``, naming the op, the batch axis
position of its batched arguments, and whether the program reduces over
the batch axis (in which case the sharded lowering completes its
batch-global sums through XLA-inserted ``psum``\\ s and the supervisor
must never split the batch — see ``device_supervisor.NO_SPLIT_OPS``).

Consumed three ways:

- the **sharding-readiness static pass** (``scripts/analysis/sharding_pass.py``)
  reads this file via ``ast.literal_eval`` (check_static stays import-free
  of ``lighthouse_tpu``) and fails when a jitted entry point in ``ops/`` is
  missing here, or when code inside a registered entry folds the batch
  axis into limb axes;
- the mesh layer's ``ShardedEntry``, one per program of ``ops/dispatch.py``
  — the consumer this registry was written for — derives each entry's ``NamedSharding``/``PartitionSpec``\\ s
  mechanically from ``batch_axis``/``batched_args``/``replicated_args``/
  ``out_batched`` instead of hand-maintaining them;
- the HLO budget auditor (``scripts/analysis/hlo_budget.py``) keys its
  per-(op, backend, bucket, mesh) StableHLO budgets on the ``op`` names
  declared here.

Keys are ``"<repo-relative path>:<function name>"``.  ``batch_axis`` is the
axis of every *batched* argument that a mesh shards (non-batched arguments
are listed under ``replicated_args`` — broadcast to every device).
``out_batched`` declares whether the program's OUTPUTS keep the batch axis
(sharded over the mesh) or are batch-reductions (replicated) — bls_verify
reduces to one pairing value even though it is splittable, so this cannot
be inferred from ``reduces_over_batch``.  This module must stay a plain
dict literal with no imports: the static pass parses it, never imports it.
"""

#: sharding-readiness contract per jitted device entry point (see module
#: docstring; sharding_pass.py enforces completeness of this mapping).
BATCH_AXES = {
    "lighthouse_tpu/ops/verify.py:_device_verify": {
        "op": "bls_verify",
        "batch_axis": 0,
        "batched_args": ["pk", "sig", "msg", "wbits", "live", "rows"],
        "replicated_args": [],
        "reduces_over_batch": False,
        "out_batched": False,
        "notes": "per-set pairing rows; the N+1'th (-g1, W) pair is "
                 "assembled inside the program from a batch-wide MSM — a "
                 "sharded lowering psums the MSM then replicates the pair; "
                 "pk's key rows shard like the sets (rows, when given, "
                 "gathers across shards)",
    },
    "lighthouse_tpu/ops/sha256_device.py:_sha256_64byte_batch": {
        "op": "sha256_pairs",
        "batch_axis": 0,
        "batched_args": ["words"],
        "replicated_args": [],
        "reduces_over_batch": False,
        "out_batched": True,
        "notes": "embarrassingly parallel over 64-byte blocks",
    },
    "lighthouse_tpu/ops/epoch_device.py:_deltas_kernel": {
        "op": "epoch_deltas",
        "batch_axis": 0,
        "batched_args": [
            "eff_bal", "activation_epoch", "exit_epoch",
            "withdrawable_epoch", "slashed", "prev_part", "inactivity",
        ],
        "replicated_args": [
            "previous_epoch", "base_reward_per_increment",
            "total_active_balance", "increment", "inactivity_score_bias",
            "inactivity_score_recovery_rate", "quotient",
        ],
        "reduces_over_batch": True,
        "out_batched": True,
        "notes": "participating-increment sums span the whole registry "
                 "(NO_SPLIT_OPS); sharding needs a psum per flag index",
    },
    "lighthouse_tpu/ops/tree_hash.py:_tree_hash_subtrees": {
        "op": "tree_hash",
        "batch_axis": 0,
        "batched_args": ["leaves"],
        "replicated_args": [],
        "reduces_over_batch": False,
        "out_batched": True,
        "notes": "fused depth-5 Merkle subtrees; embarrassingly parallel "
                 "over the subtree axis (every output level keeps it)",
    },
    "lighthouse_tpu/ops/kzg_device.py:_device_kzg_batch": {
        "op": "kzg_batch",
        "batch_axis": 0,
        "batched_args": ["c", "p", "r_bits", "rz_bits"],
        "replicated_args": ["ry_bits", "tau", "g2gen"],
        "reduces_over_batch": True,
        "out_batched": False,
        "notes": "tree-sum lincombs reduce the blob axis into one "
                 "2-pairing; sharding needs a collective point-sum",
    },
    "lighthouse_tpu/ops/shuffle_device.py:_shuffle_kernel": {
        "op": "shuffle",
        "batch_axis": 0,
        "batched_args": ["values"],
        "replicated_args": ["pivots", "digests", "n_live"],
        "reduces_over_batch": True,
        "out_batched": True,
        "notes": "swap-or-not rounds gather partner lanes across the whole "
                 "index array (a[flip]) — a sharded lowering needs "
                 "cross-shard gathers every round, so the supervisor must "
                 "never split the batch (NO_SPLIT_OPS)",
    },
    "lighthouse_tpu/ops/shuffle_device.py:_proposer_kernel": {
        "op": "proposer_select",
        "batch_axis": 0,
        "batched_args": ["eff_act"],
        "replicated_args": [
            "seed_words", "pivots", "rbytes", "m_live", "max_eb",
        ],
        "reduces_over_batch": True,
        "out_batched": False,
        "notes": "the candidate walk gathers effective balances at "
                 "shuffle-derived positions spanning the whole active "
                 "list; outputs are (S,) per-slot scalars",
    },
    "lighthouse_tpu/ops/shuffle_device.py:_boundary_kernel": {
        "op": "epoch_boundary",
        "batch_axis": 0,
        "batched_args": [
            "eff_bal", "activation_epoch", "exit_epoch",
            "withdrawable_epoch", "slashed", "prev_part", "inactivity",
            "balance", "act_elig_epoch", "eb_cap", "active_idx",
        ],
        "replicated_args": [
            "sh_pivots", "sh_digests", "seed_words", "prop_pivots",
            "rbytes", "previous_epoch", "base_reward_per_increment",
            "total_active_balance", "increment", "inactivity_score_bias",
            "inactivity_score_recovery_rate", "quotient", "current_epoch",
            "downward", "upward", "ejection_balance", "far_future",
            "finalized_epoch", "max_eb", "queue_lo", "queue_hi", "m_live",
        ],
        "reduces_over_batch": True,
        "out_batched": [
            True, True, True, True, True, True,  # per-validator arrays
            True,          # shuffled active list (same padded batch axis)
            False, False,  # per-slot proposer table + found flags
        ],
        "notes": "fused boundary: deltas sums span the registry AND the "
                 "shuffle/proposer stages gather across lanes — "
                 "NO_SPLIT_OPS; mixed out_batched list (6 per-validator "
                 "outputs + shuffled batched, proposer/found replicated)",
    },
}
