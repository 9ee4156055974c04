"""Device time by named stage.  Each ``XLA Ops`` event of a program's
execution is named to its stage by the program's own map,
``lighthouse_tpu.device_telemetry.program_stages`` (the ``jax.named_scope``
of each instruction, from the compiled program's HLO metadata), for the
bucket shapes the flight recorder saw.  A program without that map reads
None, and so does one whose ops name none of the stages asked for."""

import bisect

from benchmark.metrics._common import median


def stage_seconds(trace, needle, stage_of):
    """Per execution of the program whose module name holds ``needle``,
    inside the window: ``{stage: seconds}`` of device time.  An op nested in
    another counts once, in the outer op's stage; where ops overlap, the
    time counts once, for the op that started first.  Ops of no stage count
    under None."""
    out = []
    for plane, modules in sorted(trace.modules.items()):
        ops = sorted(trace.ops.get(plane, []), key=lambda o: (o[1], -o[2]))
        starts = [o[1] for o in ops]
        for name, m_start, m_end in modules:
            if needle not in name or m_start < trace.lo or m_end > trace.hi:
                continue
            by_stage = {}
            at = m_start
            for op, s, e in ops[bisect.bisect_left(starts, m_start):
                                bisect.bisect_right(starts, m_end)]:
                s, e = max(s, at), min(e, m_end)
                if e <= s:
                    continue
                stage = stage_of.get(op)
                by_stage[stage] = by_stage.get(stage, 0.0) + (e - s) / 1e9
                at = e
            out.append(by_stage)
    return out


def program_stage_map(ctx, op):
    """Instruction name -> stage for ``op`` at every single-device shape
    of the flight records, or None where the program has no such map."""
    try:
        from lighthouse_tpu.device_telemetry import program_stages
    except ImportError:
        return None
    shapes = sorted({r["shape"] for r in ctx.get("flight") or []
                     if r.get("op") == op and not r.get("host_fallback")
                     and "@" not in r["shape"]})
    merged = {}
    for label in shapes:
        for name, stage in (program_stages(op, tuple(int(d) for d in label.split("x")))
                            or {}).items():
            merged.setdefault(name, stage)
    return merged or None


def stage_ms(ctx, op, needle, stages):
    """Median over executions of the device time in ``stages``, in ms."""
    trace = ctx.get("device")
    if trace is None:
        return None
    stage_of = program_stage_map(ctx, op)
    if not stage_of:
        return None
    return median([sum(t for s, t in ex.items() if s in stages) * 1e3
                   for ex in stage_seconds(trace, needle, stage_of)
                   if any(s in stages for s in ex)])
