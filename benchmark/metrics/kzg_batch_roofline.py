"""Device program: the least time the chip could take for the traced DA
checks' live work (``benchmark/work/kzg_batch.py``: their blobs as the
traffic made them) over the time ``_device_kzg_batch`` ran."""

from benchmark.metrics._common import roofline
from benchmark.work import kzg_batch


def read(ctx):
    return roofline(ctx, "_device_kzg_batch", kzg_batch, lambda w: (w["blobs"],))
