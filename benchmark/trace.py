"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The device's time comes from the device planes of the ``.xplane.pb`` file
(``/device:TPU:<n>``): the ``XLA Modules`` line has one event per program
execution, the ``XLA Ops`` line one per operation.  Busy time is the union of
the operation intervals inside the window, averaged over the chips traced;
the window is the host annotation the harness opens around the requests it
traces.  ``benchmark/tests`` checks the reduction on small traces, one of
them recorded on the chip.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted, disjoint."""
    out: List[list] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``: the TPU
    names an op event by its whole HLO instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "TPU" in name


class DeviceTrace:
    """Device events of one traced window."""

    def __init__(self, modules: Dict[str, List[list]], ops: Dict[str, List[list]],
                 lo: float, hi: float):
        # modules/ops: plane name -> [[name, start_ns, end_ns], ...]
        self.modules, self.ops = modules, ops
        self.lo, self.hi = lo, hi
        self.n_chips = max(1, len(set(modules) | set(ops)))
        self.busy = {plane: union([(s, e) for _, s, e in
                                   (ops.get(plane) or modules.get(plane, []))],
                                  lo, hi)
                     for plane in set(modules) | set(ops)}

    @classmethod
    def from_dir(cls, path: str, window: str) -> "DeviceTrace":
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(path, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"no profiler trace under {path}")
        return cls.from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)),
                                window)

    @classmethod
    def from_profile(cls, pd, window: str) -> "DeviceTrace":
        lo = hi = None
        modules: Dict[str, List[list]] = {}
        ops: Dict[str, List[list]] = {}
        for plane in pd.planes:
            device = is_device_plane(plane.name)
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if not device:
                        if ev.name == window and lo is None:
                            lo, hi = s, e
                        continue
                    if line.name == "XLA Modules":
                        modules.setdefault(plane.name, []).append([ev.name, s, e])
                    elif line.name == "XLA Ops":
                        ops.setdefault(plane.name, []).append(
                            [op_name(ev.name), s, e])
        if lo is None:
            raise RuntimeError(f"window annotation {window!r} not in the trace")
        return cls(modules, ops, lo, hi)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        total = sum(e - s for ivs in self.busy.values() for s, e in ivs)
        return total / 1e9 / self.n_chips

    def module_seconds(self, needle: str) -> List[float]:
        """Durations of the program executions whose module name holds
        ``needle``, inside the window, on every chip."""
        return [(e - s) / 1e9 for evs in self.modules.values()
                for name, s, e in evs
                if needle in name and s >= self.lo and e <= self.hi]
