"""From a profiler trace to busy time, kernel time and idle gaps.

The reduction works on plain intervals so that it can be checked on a
constructed trace; :func:`read_xplane` turns the ``.xplane.pb`` file the JAX
profiler writes into those intervals.

* busy: the union of the intervals in which an operation runs on a device,
  clipped to the window, averaged over the devices;
* kernel time: the summed device durations of the events whose name holds a
  kernel's name;
* the device ops that took most time, each by its self time: its duration
  less that of the ops nested in it (a ``while`` op spans its whole body);
* idle gaps: the stretches of the window in which a device runs nothing,
  each named by the host activity that overlaps it most.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # seconds, on the trace's clock
    end: float


def merged(intervals: Iterable[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Union of ``intervals`` clipped to [lo, hi], as sorted disjoint runs."""
    runs: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if runs and s <= runs[-1][1]:
            runs[-1] = (runs[-1][0], max(runs[-1][1], e))
        else:
            runs.append((s, e))
    return runs


def busy_seconds(ops: Sequence[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(((o.start, o.end) for o in ops),
                                        lo, hi))


def idle_gaps(ops: Sequence[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no op runs, longest first."""
    gaps, t = [], lo
    for s, e in merged(((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in ``gap``: the innermost (shortest) host
    event that overlaps at least half of it, else the one that overlaps it
    most, else ``idle``."""
    length = gap[1] - gap[0]
    overlaps = [(min(gap[1], h.end) - max(gap[0], h.start), h) for h in host]
    overlaps = [(o, h) for o, h in overlaps if o > 0]
    if not overlaps:
        return "idle"
    half = [h for o, h in overlaps if o >= length / 2]
    if half:
        return min(half, key=lambda h: h.end - h.start).name
    return max(overlaps, key=lambda oh: oh[0])[1].name


def op_totals(ops: Sequence[Event], lo: float, hi: float) -> dict:
    """{op name: self seconds in [lo, hi]}: each op's clipped duration less
    that of the ops nested directly in it."""
    out: dict = defaultdict(float)
    stack: List[Event] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= o.start:
            stack.pop()
        d = max(0.0, min(o.end, hi) - max(o.start, lo))
        out[o.name] += d
        if stack and o.end <= stack[-1].end:
            out[stack[-1].name] -= d
        stack.append(o)
    return {n: s for n, s in out.items() if s > 0}


def kernel_time(ops: Sequence[Event], needle: str, lo: float,
                hi: float) -> Tuple[float, int]:
    """(seconds, calls) of the ops whose name holds ``needle``."""
    hits = [o for o in ops if needle in o.name
            and min(o.end, hi) > max(o.start, lo)]
    return sum(min(o.end, hi) - max(o.start, lo) for o in hits), len(hits)


def summarize(devices: Sequence[Sequence[Event]], host: Sequence[Event],
              lo: float, hi: float, top: int = 10) -> dict:
    """Busy seconds averaged over devices, and the breakdown of the first."""
    busy = sum(busy_seconds(ops, lo, hi) for ops in devices) / len(devices)
    ops0 = devices[0]
    totals = sorted(op_totals(ops0, lo, hi).items(), key=lambda kv: -kv[1])
    gaps = [[host_activity(g, host), g[1] - g[0]]
            for g in idle_gaps(ops0, lo, hi)[:top]]
    return {"busy_s": busy, "window_s": hi - lo,
            "device_ops": [[n, s] for n, s in totals[:top]],
            "idle_gaps": gaps}


def op_name(name: str) -> str:
    """The instruction's name: a TPU trace names a device op by its whole
    HLO line, ``%ssd_scan.32 = (f32[...]) custom-call(...), ...``."""
    return name[1:].split(" = ", 1)[0] if name.startswith("%") else name


def read_xplane(path: Path) -> Tuple[List[List[Event]], List[Event]]:
    """(ops of each device, host events) from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [Event(op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
        elif plane.name == HOST_PLANE:
            host.extend(Event(ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                        for line in plane.lines for ev in line.events)
    return devices, host


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]
