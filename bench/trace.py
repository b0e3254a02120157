"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

A trace holds host threads and, on a chip, one plane per device. On a
device plane the ``XLA Ops`` line has one event per operation that ran and
the ``XLA Modules`` line one per program (jitted function) run. On the CPU
backend, which the tests use, operations run on host threads; there an
event that names an ``hlo_op`` is an operation, of the device given by its
``device_ordinal``, and its ``hlo_module`` stat names its program.

What is read:

- the window: the host annotation :data:`WINDOW` (the benchmark's own span
  around the measured ``train`` call);
- each device's operations inside the window, with their program;
- busy time: the union of a device's operation intervals (what runs on the
  device, not what the host dispatches);
- idle gaps: the window less the busy union, each named by the host event
  that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Op:
    name: str
    module: str
    start: int  # ns
    end: int
    stats: dict = field(default_factory=dict)


@dataclass
class Trace:
    window: Tuple[int, int]
    ops: Dict[str, List[Op]]  # device -> operations in the window
    host: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 — a stat of a type the reader lacks
        return {}


def _module_name(name: str) -> str:
    """``jit_window_step(12)`` -> ``jit_window_step``."""
    return _MODULE_ID.sub("", name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(path).planes)


def from_planes(planes) -> Trace:
    """The trace of profiler planes (each with ``name`` and ``lines``; a
    line with ``name`` and ``events``; an event with ``name``,
    ``start_ns``, ``duration_ns`` and ``stats``)."""
    window: Optional[Tuple[int, int]] = None
    host: List[Tuple[str, int, int]] = []
    raw: Dict[str, List[Op]] = defaultdict(list)
    for plane in planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device:
                if line.name == "XLA Modules":
                    mods = [( _module_name(ev.name), int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns))
                            for ev in line.events]
                    raw[plane.name + "#modules"] = [
                        Op(m, m, s, e) for m, s, e in mods]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        st = _stats(ev)
                        raw[plane.name].append(Op(
                            ev.name, str(st.get("hlo_module", "")),
                            int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns), st))
                continue
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW:
                    window = (s, e)
                    continue
                st = _stats(ev)
                if "hlo_op" in st:
                    dev = f"/device:CPU:{st.get('device_ordinal', 0)}"
                    raw[dev].append(Op(str(st["hlo_op"]),
                                       str(st.get("hlo_module", "")), s, e,
                                       st))
                elif e > s:
                    host.append((ev.name, s, e))
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} span in the trace")
    ops: Dict[str, List[Op]] = {}
    for dev, evs in raw.items():
        if dev.endswith("#modules"):
            continue
        mods = raw.get(dev + "#modules", [])
        inside = [o for o in evs if o.end > window[0] and o.start < window[1]]
        if mods:
            _attach_modules(inside, mods)
        ops[dev] = sorted(inside, key=lambda o: o.start)
    if not ops:
        raise RuntimeError("no device operation in the trace")
    return Trace(window, ops, host)


def _attach_modules(ops: List[Op], mods: List[Op]) -> None:
    """Give each operation with no module stat the program run enclosing it."""
    mods = sorted(mods, key=lambda m: m.start)
    starts = [m.start for m in mods]
    import bisect

    for o in ops:
        if o.module:
            continue
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and mods[i].end >= o.end:
            o.module = mods[i].name


def clip(o: Op, window: Tuple[int, int]) -> Tuple[int, int]:
    return max(o.start, window[0]), min(o.end, window[1])


def length(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Tuple[int, int]], b: List[Tuple[int, int]]
              ) -> List[Tuple[int, int]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy(trace: Trace, device: str) -> List[Tuple[int, int]]:
    return union(clip(o, trace.window) for o in trace.ops[device])


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the devices."""
    tot = [sum(e - s for s, e in busy(trace, d)) for d in trace.devices]
    return sum(tot) / len(tot) * 1e-9


def idle_share(trace: Trace, device: str) -> float:
    b = sum(e - s for s, e in busy(trace, device))
    return 1.0 - b / (trace.window[1] - trace.window[0])


def op_seconds(trace: Trace, device: str, match) -> float:
    """Seconds of the device's operations for which ``match(op)`` holds
    (overlapping operations counted once)."""
    return length(union(clip(o, trace.window)
                        for o in trace.ops[device] if match(o))) * 1e-9


def idle_gaps(trace: Trace, device: str, n: int = 10
              ) -> List[Tuple[str, float]]:
    """The window's ``n`` longest idle gaps on ``device``, longest first,
    each named by the host event that overlaps it most (``"?"`` where none
    does; the benchmark's window span itself does not count)."""
    gaps, t = [], trace.window[0]
    for s, e in busy(trace, device) + [(trace.window[1], trace.window[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        best, best_ov = "?", 0
        for name, hs, he in trace.host:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append((best, (e - s) * 1e-9))
    return out


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Operations that took the most device time, summed by name over the
    devices and averaged over them."""
    tot: Dict[str, float] = defaultdict(float)
    for d in trace.devices:
        for o in trace.ops[d]:
            s, e = clip(o, trace.window)
            tot[o.name] += (e - s) * 1e-9 / len(trace.devices)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def describe(path: str, limit: int = 40) -> str:
    """Plane, line and sample event names with their stats: for reading a
    new trace by hand before writing code against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name} ({len(evs)} events)")
            for ev in evs[:limit]:
                out.append(f"    {ev.name} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {_stats(ev)}")
    return "\n".join(out)
