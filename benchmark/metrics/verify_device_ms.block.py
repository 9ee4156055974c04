"""Device program: median device time of one ``_device_verify`` execution,
from the ``XLA Modules`` events of the profiler trace."""

from benchmark.metrics._common import device_ms


def read(ctx):
    return device_ms(ctx, "_device_verify")
