"""Device program: median device time per ``_device_verify`` execution of
the ops that sum each set's public keys: the key rows' tree sums
(``s1_key_sum``) and each set's sum of its rows (``s1_set_sum``)."""

from benchmark.metrics._stages import stage_ms

STAGES = ("s1_key_sum", "s1_set_sum")


def read(ctx):
    return stage_ms(ctx, "bls_verify", "_device_verify", STAGES)
