"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Reads the trace with ``jax.profiler.ProfileData`` alone.  Device planes
are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one
event per program execution and the ``XLA Ops`` line one per operation
(fusions, custom calls — the Pallas kernels — copies).  Host planes hold
the harness's spans (``jax.profiler.TraceAnnotation``) on the threads
that opened them.

* busy time — the union of a device's operation intervals, averaged
  over the devices that ran anything;
* module and operation time — summed durations, keyed by name;
* idle gaps — the intervals between busy runs, each named by the
  shortest host span that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds from the trace's origin
    dur: float          # seconds
    detail: str = ""    # every string stat, joined: long names, scopes

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[List[Event]]          # per device that ran anything
    modules: List[List[Event]]
    host: List[Event]
    span: Tuple[float, float]       # first start, last end (devices)

    @property
    def window_s(self) -> float:
        return self.span[1] - self.span[0]


def _event(ev) -> Event:
    detail = " ".join(str(v) for _, v in ev.stats if isinstance(v, str))
    return Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, detail)


def find_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def from_profile(pd) -> Trace:
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [_event(e) for e in line.events]
                     for line in plane.lines}
            if lines.get(OPS_LINE):
                ops.append(sorted(lines[OPS_LINE], key=lambda e: e.start))
                modules.append(sorted(lines.get(MODULES_LINE, []),
                                      key=lambda e: e.start))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_event(e) for e in line.events if e.duration_ns)
    if not ops:
        raise ValueError("the trace holds no device operations")
    starts = [d[0].start for d in ops]
    ends = [max(e.end for e in d) for d in ops]
    return Trace(ops, modules, host, (min(starts), max(ends)))


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(find_file(trace_dir)))


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds with an operation running, averaged over devices."""
    return sum(b - a for dev in tr.ops for a, b in union(dev)) / len(tr.ops)


def short(name: str) -> str:
    """An operation's HLO text cut to its name, output shape and kind:
    ``%fusion.110 f32[4,64,126464] fusion``."""
    lhs, _, rhs = name.partition(" = ")
    m = re.match(r"(\(.*?\)|\S+) ([\w-]+)\(", re.sub(r"\{[^}]*\}", "", rhs))
    if not m:
        return name[:120]
    out = m.group(1) if len(m.group(1)) <= 60 else m.group(1)[:57] + "..."
    return f"{lhs} {out} {m.group(2)}"


def by_name(events: Iterable[Event], key=lambda n: n) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        k = key(e.name)
        out[k] = out.get(k, 0.0) + e.dur
    return out


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    """Events whose name or string stats match ``pattern`` (a regex)."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name) or rx.search(e.detail)]


def inside(ops: List[Event], modules: List[Event]) -> List[Event]:
    """The operations that run inside any of ``modules``."""
    spans = union(modules)
    out, j = [], 0
    for e in ops:
        while j < len(spans) and spans[j][1] < e.start:
            j += 1
        if j < len(spans) and spans[j][0] <= e.start <= spans[j][1]:
            out.append(e)
    return out


def idle_gaps(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first device, each named by the
    shortest host span covering its midpoint."""
    busy = union(tr.ops[0])
    gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [h for h in tr.host if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.dur).name if cover else "(no span)"
        out.append((name, b - a))
    return out
