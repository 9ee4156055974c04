"""Host marshal: median ``kzg_host_prep`` span per DA check (parsing and
subgroup checks, challenges, evaluation, the batch's random powers)."""

from benchmark.metrics._common import span_ms


def read(ctx):
    return span_ms(ctx, "kzg_host_prep")
