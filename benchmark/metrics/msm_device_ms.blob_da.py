"""Device program: median device time per ``_device_kzg_batch`` execution
of the ops in its stages k1-k2 (the four bit ladders, the tree sums and
adds)."""

from benchmark.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "kzg_batch", "_device_kzg_batch",
                    ("k1_g1_ladders", "k2_lincomb"))
