"""Host marshal: median ``kzg_evaluate`` span per DA check (each blob's
polynomial evaluated at its challenge)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "kzg_evaluate")
