"""Host marshal: median ``marshal_pack`` span per batch (the limbs of
signatures, messages, keys and weight bits, into the padded bucket)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "marshal_pack")
