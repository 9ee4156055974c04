"""Device program: median device time per ``_device_verify`` execution of
the ops in its pairing (``miller_loop``, ``final_exp``)."""

from benchmark.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "bls_verify", "_device_verify",
                    ("miller_loop", "final_exp"))
