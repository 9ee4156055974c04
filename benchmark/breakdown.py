"""The ``breakdown`` of a ``--trace 1`` result line: the device ops that took
most time, and the longest idle gaps of the chip, each named by what the
host was doing then.  No per-layer metric reads it; the driver copies it
into the ledger, where it is the trace the writer of the next issue sees.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from benchmark.trace import DeviceTrace, Interval


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` around the sorted disjoint ``busy``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: Interval, intervals: Sequence[Interval]) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in intervals)


def label_gap(gap: Interval, host: Dict[str, List[Interval]],
              default: str) -> str:
    """The host activity that covers most of ``gap``, or ``default``."""
    best, best_ns = default, 0.0
    for name, ivs in host.items():
        ns = overlap(gap, ivs)
        if ns > best_ns:
            best, best_ns = name, ns
    return best


#: Program span -> the host activity it stands for in an idle gap.
HOST_SPANS = {"device_batch_setup": "host_marshal",
              "device_batch_dispatch": "dispatch",
              "device_batch_verdict": "verdict_on_host"}


def host_activity(spans: dict, to_ns) -> Dict[str, List[Interval]]:
    out: Dict[str, List[Interval]] = {}
    for span_name, label in HOST_SPANS.items():
        for sp in spans.get(span_name, []):
            if sp["end_pc"] is not None:
                out.setdefault(label, []).append((to_ns(sp["start_pc"]),
                                                  to_ns(sp["end_pc"])))
    for sp in spans.get("pipeline_batch", []):
        # linger: from the oldest group's submit to the batch being taken
        linger = float(sp["fields"].get("linger_s") or 0.0)
        build = [b for b in spans.get("pipeline_build", [])
                 if b["start_pc"] <= sp["start_pc"]]
        if build and linger:
            taken = max(b["start_pc"] for b in build)
            out.setdefault("pipeline_linger", []).append(
                (to_ns(taken - linger), to_ns(taken)))
    return out


def breakdown(trace: DeviceTrace, spans: dict, requests: Sequence[Interval],
              start_pc: float, top: int = 10) -> dict:
    """``spans`` and ``requests`` are on the host's perf counter, whose
    ``start_pc`` is the trace window's start."""
    def to_ns(pc):
        return trace.lo + (pc - start_pc) * 1e9

    per_op: Dict[str, float] = {}
    for evs in trace.ops.values():
        for name, s, e in evs:
            if s >= trace.lo and e <= trace.hi:
                per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    host = host_activity(spans, to_ns)
    in_call = [(to_ns(a), to_ns(b)) for a, b in requests]
    idle = []
    plane = sorted(trace.busy)[0] if trace.busy else None
    for g in gaps(trace.busy.get(plane, []), trace.lo, trace.hi):
        default = ("in_call_other" if overlap(g, in_call) > (g[1] - g[0]) / 2
                   else "between_requests")
        idle.append([label_gap(g, host, default), (g[1] - g[0]) / 1e9])
    idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in device_ops], "idle_gaps": idle[:top]}
