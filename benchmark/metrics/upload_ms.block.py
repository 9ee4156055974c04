"""Host marshal: median ``marshal_upload`` span per batch (the packed
arrays placed on the device)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "marshal_upload")
