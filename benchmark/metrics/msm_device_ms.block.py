"""Device program: median device time per ``_device_verify`` execution of
the ops in its stages s1-s3 (key tree-sum, G1 weight ladder, G2 MSM with
the affine inversion)."""

from benchmark.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "bls_verify", "_device_verify",
                    ("s1_key_sum", "s2_g1_ladder", "s3_g2_msm"))
