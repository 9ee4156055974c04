"""Device pipeline: median time from a block's submit until the builder takes
its batch, linger included (``pipeline_batch`` span, field ``linger_s``)."""

from benchmark.metrics._common import median


def read(ctx):
    return median([float(s["fields"]["linger_s"]) * 1e3
                   for s in ctx["spans"].get("pipeline_batch", [])
                   if "linger_s" in s["fields"]])
