"""Helpers the per-layer readers share.  A reader returns None where it
finds nothing to read; the harness then leaves its metric out."""

import statistics

from benchmark.work import fp


def median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def span_ms(ctx, name):
    return median([s["duration"] * 1e3 for s in ctx["spans"].get(name, [])])


def device_ms(ctx, needle):
    trace = ctx.get("device")
    return None if trace is None else median(
        [s * 1e3 for s in trace.module_seconds(needle)])


def idle_pct(ctx):
    trace = ctx.get("device")
    if trace is None or not trace.busy or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)


def device_s_total(ctx, needle):
    trace = ctx.get("device")
    return None if trace is None else sum(trace.module_seconds(needle)) or None


def roofline(ctx, needle, model, sizes):
    """The least time the chip could take for the live work of the requests
    the trace holds, over the time the program ``needle`` ran in it.  The
    work is counted from each request's live sizes as the traffic made it
    (``ctx["work"]``), by ``model`` (``benchmark/work/<kernel>.py``), so it
    is the same whatever implements it."""
    seconds = device_s_total(ctx, needle)
    work = ctx.get("work") or []
    if not seconds or not work:
        return None
    ops = sum(model.ops(*sizes(w)) for w in work)
    nbytes = sum(model.nbytes(*sizes(w)) for w in work)
    return fp.roofline_pct(ops, nbytes, seconds, peak(ctx))[0]


def peak(ctx):
    from benchmark.peaks import peaks

    return peaks(ctx["device_kind"])
