"""JAX/TPU batch-verification backend.

The slot of ``crypto/bls/src/impls/blst.rs`` in the reference: all signature
sets in the node funnel through here, and the multi-pairing runs as a fused,
shape-bucketed device program (``lighthouse_tpu/ops/verify.py``).
"""

from __future__ import annotations

from typing import Optional

_platform: Optional[str] = None


def _device_platform() -> str:
    """The executing device platform (cached — jax.devices() is cheap after
    backend init, but the span field should cost a dict lookup, not a
    client call, on every batch)."""
    global _platform
    if _platform is None:
        import jax

        _platform = jax.devices()[0].platform
    return _platform


def verify_signature_sets(sets, seed: Optional[bytes] = None) -> bool:
    from .... import tracing
    from ....ops.verify import verify_signature_sets_device

    sets = list(sets)
    # The device-side parent span: the four stage spans recorded inside
    # verify_signature_sets_device (setup/dispatch/wait/verdict) nest here,
    # and the callee stamps its flight-recorder seq (and host-fallback flag,
    # when taken) onto this span — so a trace tree and the
    # /lighthouse/device/batches ring cross-reference in both directions.
    with tracing.span(
        "device_verify", backend="jax", platform=_device_platform(),
        n_sets=len(sets),
    ):
        return verify_signature_sets_device(sets, seed=seed)
