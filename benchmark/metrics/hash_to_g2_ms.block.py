"""Host marshal: median ``marshal_hash_to_g2`` span per batch (every
set's message hashed to G2, behind the bounded cache)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "marshal_hash_to_g2")
