"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per TPU chip, the busy time (union of the intervals in
which a device operation ran), device time per operation name, per
replay kernel (``pallas_call`` name) and per collective kind, and the
longest idle gaps with what the host was doing in each.

    python3 perfbench/trace_reduce.py <file.xplane.pb | dir>   # summary

The reduction works on plain tuples, so tests can feed it synthetic
planes as well as a recorded trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
REPLAY_KERNELS = ("sumtree_update", "sumtree_sample", "sample_gather",
                  "gather_rows")
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

# (name, start_ns, duration_ns, {stat: value})
Event = Tuple[str, float, float, Dict[str, object]]
# (line name, events)
Line = Tuple[str, Sequence[Event]]
# (plane name, lines)
Plane = Tuple[str, Sequence[Line]]


@dataclasses.dataclass
class DeviceStats:
    name: str
    busy_ns: float
    first_ns: float
    last_ns: float
    op_ns: Dict[str, float]             # self time: less nested ops
    kernel_ns: Dict[str, float]
    kernel_calls: Dict[str, int]
    collective_ns: Dict[str, float]
    gaps: List[Tuple[float, float]]     # idle (start, end), longest first


@dataclasses.dataclass
class Reduced:
    devices: List[DeviceStats]
    host_events: List[Tuple[str, float, float]]   # (name, start, end)

    @property
    def busy_s_mean(self) -> float:
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def kernel_s(self, name: str) -> float:
        """Summed over chips."""
        return sum(d.kernel_ns.get(name, 0.0) for d in self.devices) / 1e9

    def kernel_calls(self, name: str) -> int:
        return sum(d.kernel_calls.get(name, 0) for d in self.devices)

    def collective_s(self) -> float:
        return sum(sum(d.collective_ns.values()) for d in self.devices) / 1e9

    def busy_s_total(self) -> float:
        return sum(d.busy_ns for d in self.devices) / 1e9


def union_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint, sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(name: str) -> str:
    """An op event's short name.  A TPU trace may name an op by its
    whole HLO instruction (``%sample_gather.23 = (...) custom-call(...)``);
    the short name is the instruction's own (``sample_gather.23``)."""
    name = name.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def _named(name: str, kernel: str) -> bool:
    return name.startswith(kernel) and (
        len(name) == len(kernel) or not name[len(kernel)].isalnum())


def kernel_of(event: Event):
    """The replay kernel an op event belongs to, or None.  A
    ``pallas_call`` keeps its ``name=`` as the op's name (or its
    prefix); a stat carries it where the op was renamed.  Only an
    instruction's own name counts, not the operands a stat may list."""
    name, _, _, stats = event
    names = [op_name(name)] + [op_name(v) for v in stats.values()
                               if isinstance(v, str)]
    for n in names:
        for k in REPLAY_KERNELS:
            if _named(n, k):
                return k
    return None


_OPCODE = re.compile(r"(?<![\w.%-])([a-z][a-z0-9-]*)\(")


def op_code(text: str):
    """The opcode of a whole HLO instruction (``%psum.7 = f32[256]
    all-reduce(%fusion), ...`` gives ``all-reduce``), or None."""
    if " = " not in text:
        return None
    m = _OPCODE.search(text.split(" = ", 1)[1])
    return m.group(1) if m else None


def collective_of(event: Event):
    """The collective kind of an op event, or None: by the opcode where
    the name or a stat holds the whole instruction (the instruction
    may be named for what made it, ``psum.7``), else by the name."""
    name, _, _, stats = event
    texts = [name] + [v for v in stats.values() if isinstance(v, str)]
    codes = [c for c in map(op_code, texts) if c]
    for text in codes or [op_name(name)]:
        for kind in COLLECTIVE_KINDS:
            if text.startswith(kind):
                return kind
    return None


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less that of the events nested in it.  A
    TPU trace puts a loop (``while``, ``conditional``) on the same line
    as the ops it runs, so the loop's own time is what is left."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [float(ev[2]) for ev in events]
    stack: List[int] = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][1] + events[stack[-1]][2]:
            own[stack[-1]] -= events[i][2]
        stack.append(i)
    return own


def _device_stats(plane_name: str, lines: Sequence[Line]) -> DeviceStats:
    events: List[Event] = []
    for line_name, evs in lines:
        if line_name == OPS_LINE:
            events.extend(evs)
    spans = union_intervals((s, s + d) for _, s, d, _ in events)
    op_ns: Dict[str, float] = {}
    kernel_ns: Dict[str, float] = {}
    kernel_calls: Dict[str, int] = {}
    coll_ns: Dict[str, float] = {}
    for ev, own in zip(events, self_times(events)):
        name, dur = op_name(ev[0]), ev[2]
        op_ns[name] = op_ns.get(name, 0.0) + own
        k = kernel_of(ev)
        if k is not None:
            kernel_ns[k] = kernel_ns.get(k, 0.0) + dur
            kernel_calls[k] = kernel_calls.get(k, 0) + 1
        c = collective_of(ev)
        if c is not None:
            coll_ns[c] = coll_ns.get(c, 0.0) + dur
    gaps = [(a[1], b[0]) for a, b in zip(spans, spans[1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    return DeviceStats(
        name=plane_name,
        busy_ns=sum(e - s for s, e in spans),
        first_ns=spans[0][0] if spans else 0.0,
        last_ns=spans[-1][1] if spans else 0.0,
        op_ns=op_ns, kernel_ns=kernel_ns,
        kernel_calls=kernel_calls, collective_ns=coll_ns, gaps=gaps)


def reduce_planes(planes: Iterable[Plane]) -> Reduced:
    devices, host = [], []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            stats = _device_stats(pname, lines)
            if stats.op_ns:
                devices.append(stats)
        elif pname.startswith(HOST_PREFIX):
            for _, evs in lines:
                host.extend((n, s, s + d) for n, s, d, _ in evs if d > 0)
    devices.sort(key=lambda d: d.name)
    return Reduced(devices=devices, host_events=host)


def planes_of(profile) -> List[Plane]:
    """Plain tuples from a ``jax.profiler.ProfileData``.  A TPU v5e
    trace holds some 350,000 op events per chunk of the XLA cells."""
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            # an event named by its whole instruction needs no stats
            evs = [(e.name, float(e.start_ns), float(e.duration_ns),
                    {} if " = " in e.name else dict(e.stats))
                   for e in line.events]
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(planes_of(ProfileData.from_file(find_xplane(path))))


def host_activity(reduced: Reduced, t_ns: float) -> str:
    """The innermost host span open at ``t_ns``, or ``host idle``."""
    best = None
    for name, s, e in reduced.host_events:
        if s <= t_ns <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host idle"


def breakdown(reduced: Reduced, top: int = 10) -> dict:
    """Device operations that took most time (self time in seconds,
    mean per chip) and the longest idle gaps of the first chip, by host
    activity."""
    n = len(reduced.devices)
    ops: Dict[str, float] = {}
    for d in reduced.devices:
        for name, ns in d.op_ns.items():
            ops[name] = ops.get(name, 0.0) + ns / n / 1e9
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if reduced.devices:
        for s, e in reduced.devices[0].gaps[:top]:
            gaps.append([host_activity(reduced, (s + e) / 2), (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": gaps}


def _summary(path: str) -> None:
    from jax.profiler import ProfileData

    planes = planes_of(ProfileData.from_file(find_xplane(path)))
    for pname, lines in planes:
        print(f"plane {pname}")
        for lname, evs in lines:
            print(f"  line {lname}: {len(evs)} events")
            for ev in evs[:3]:
                print(f"    {ev[0]!r} start {ev[1]:.0f} dur {ev[2]:.0f} "
                      f"stats {ev[3]}")
    red = reduce_planes(planes)
    for d in red.devices:
        print(f"{d.name}: busy {d.busy_ns / 1e9:.6f} s over "
              f"{(d.last_ns - d.first_ns) / 1e9:.6f} s; kernels "
              f"{d.kernel_ns}; collectives {d.collective_ns}")
    print(breakdown(red))


if __name__ == "__main__":
    _summary(sys.argv[1])
