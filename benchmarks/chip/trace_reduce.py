"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

- busy time: the union of the intervals in which an operation ran on a
  device (the device plane's "XLA Ops" line), clipped to the window;
- idle share: 1 - busy / window, averaged over the devices;
- program time: summed device durations of one jitted program's events
  (the "XLA Modules" line), found by a substring of its name;
- idle gaps, attributed to the benchmark's own host annotation (`bench.*`)
  that was open at the gap's midpoint.

The window is the host annotation `bench.window`.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]                       # ns
    ops: dict[str, list[tuple[str, float, float]]]    # device -> (name, s, e)
    modules: dict[str, list[tuple[str, float, float]]]
    host: list[tuple[str, float, float]]              # bench.* annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes)


def from_planes(planes) -> Trace:
    """Build a `Trace` from objects shaped like ProfileData's planes:
    `.name`, `.lines` of `.name`, `.events` of `.name`, `.start_ns`,
    `.duration_ns`."""
    ops, modules, host = {}, {}, []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[
                        plane.name] = evs
        else:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    return Trace(window=windows[0], ops=ops, modules=modules,
                 host=sorted(host, key=lambda h: h[1]))


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as disjoint sorted pieces."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the devices traced."""
    lo, hi = trace.window
    per = [sum(e - s for s, e in merge(evs, lo, hi))
           for evs in trace.ops.values()]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def idle_pct(trace: Trace) -> float | None:
    if not trace.ops:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def program_s(trace: Trace, name_part: str) -> tuple[float, int]:
    """(device seconds, events) of the programs whose name holds
    `name_part`, summed over devices and clipped to the window."""
    lo, hi = trace.window
    total, n = 0.0, 0
    for evs in trace.modules.values():
        for name, s, e in evs:
            if name_part in name and e > lo and s < hi:
                total += min(e, hi) - max(s, lo)
                n += 1
    return total * 1e-9, n


def host_at(trace: Trace, t: float) -> str:
    """Innermost benchmark annotation open at time t."""
    best = None
    for name, s, e in trace.host:
        if s > t:
            break
        if e >= t and name != WINDOW:
            best = name
    return best or "host.other"


def breakdown(trace: Trace, top: int = 10) -> dict:
    lo, hi = trace.window
    per_op: collections.Counter = collections.Counter()
    for evs in trace.ops.values():
        for name, s, e in evs:
            if e > lo and s < hi:
                per_op[name] += (min(e, hi) - max(s, lo)) * 1e-9
    n_dev = max(1, len(trace.ops))
    gaps: collections.Counter = collections.Counter()
    for evs in trace.ops.values():
        busy = merge(evs, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[host_at(trace, (s + e) / 2)] += (e - s) * 1e-9 / n_dev
    return {
        "device_ops": [[k, v / n_dev] for k, v in per_op.most_common(top)],
        "idle_gaps": [[k, v] for k, v in gaps.most_common(top)],
    }
