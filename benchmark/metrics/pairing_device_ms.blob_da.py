"""Device program: median device time per ``_device_kzg_batch`` execution
of the ops in its pairing (``miller_loop``, ``final_exp``)."""

from benchmark.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "kzg_batch", "_device_kzg_batch",
                    ("miller_loop", "final_exp"))
