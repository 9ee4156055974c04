"""Host marshal: median ``device_batch_setup`` span per batch (subgroup
checks, hash-to-G2, limb packing, upload)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "device_batch_setup")
