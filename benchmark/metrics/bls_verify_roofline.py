"""Device program: the least time the chip could take for the traced
blocks' live work (``benchmark/work/bls_verify.py``: their sets and keys as
the traffic made them) over the time ``_device_verify`` ran."""

from benchmark.metrics._common import roofline
from benchmark.work import bls_verify


def read(ctx):
    return roofline(ctx, "_device_verify", bls_verify,
                    lambda w: (w["sets"], w["keys"]))
