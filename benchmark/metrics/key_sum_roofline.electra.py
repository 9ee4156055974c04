"""Device program: the least time the chip could take to sum the traced
blocks' keys into one aggregate a set (``benchmark/work/key_sum.py``: their
sets and keys as the traffic made them, whatever the row width) over the
device time of ``_device_verify``'s stages ``s1_key_sum`` and
``s1_set_sum``."""

from benchmark.metrics._common import peak
from benchmark.metrics._stages import program_stage_map, stage_seconds
from benchmark.work import key_sum

STAGES = ("s1_key_sum", "s1_set_sum")


def read(ctx):
    trace, work = ctx.get("device"), ctx.get("work") or []
    stage_of = program_stage_map(ctx, "bls_verify") if trace is not None else None
    if not stage_of or not work:
        return None
    seconds = sum(t for ex in stage_seconds(trace, "_device_verify", stage_of)
                  for s, t in ex.items() if s in STAGES)
    if not seconds:
        return None
    n = sum(w["sets"] for w in work)
    keys = sum(w["keys"] for w in work)
    return key_sum.roofline_pct(n, keys, seconds, peak(ctx))[0]
