"""Device program: median device time per ``_device_verify`` execution of
the ops in its stage ``h2c_clear``: each message hash's cofactor cleared on
the device, and the batched inversion back to affine."""

from benchmark.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "bls_verify", "_device_verify", ("h2c_clear",))
