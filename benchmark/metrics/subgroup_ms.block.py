"""Host marshal: median ``marshal_subgroup`` span per batch (every
signature's G2 subgroup check and the empty-key check)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "marshal_subgroup")
