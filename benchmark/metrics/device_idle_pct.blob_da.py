"""Device: share of the window in which no operation ran on the chip."""

from benchmark.metrics._common import idle_pct


def read(ctx):
    return idle_pct(ctx)
