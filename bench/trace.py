"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps, the same in every run.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: on each
device plane (``/device:TPU:<id>``) the line of XLA operations, and on the
host plane every thread's events (the benchmark's ``TraceAnnotation``
spans and JAX's own dispatch events). Host and device events share the
trace's clock. What follows works on those plain (name, start, end) lists
and is tested on a small recorded trace (``bench/tests``).

  busy_s       union of the operation intervals of each device, averaged
               over the devices, in seconds
  window_s     the traced window: the benchmark's ``bench.window`` span
  op_time      summed device time of operations whose name starts with a
               given prefix (a kernel's name)
  top_ops      the operations that took most device time, by label (the
               head of the HLO text the TPU trace names each one by)
  idle_gaps    the longest stretches with no operation on a device,
               each named by what the host was doing then
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple  # (name, start ns, end ns[, label])

#: the line of a device plane that holds one event per XLA operation
OPS_LINE = "XLA Ops"
#: a host event must cover this share of a gap to name it alone
COVER = 0.5
#: the benchmark's span around the traced window; it bounds the window
WINDOW_SPAN = "bench.window"


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint, sorted union of (start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Event]]   # device id -> its XLA operations
    host: List[Event]                 # every host thread's events
    start: int
    end: int

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    @property
    def n_ops(self) -> int:
        return sum(len(v) for v in self.devices.values())

    def busy(self, device: int) -> List[Tuple[int, int]]:
        return merge([(ev[1], ev[2]) for ev in self.devices[device]])

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(length(self.busy(d)) for d in self.devices) / (
            1e9 * len(self.devices))

    def op_time(self, prefix: str) -> float:
        """Seconds of device time in operations named ``prefix``...,
        summed over the devices."""
        return sum(length(merge([(ev[1], ev[2]) for ev in evs
                                 if ev[0].startswith(prefix)]))
                   for evs in self.devices.values()) / 1e9

    def top_ops(self, n: int) -> List[list]:
        total: Dict[str, int] = {}
        for ops in self.devices.values():
            for ev in ops:
                label = ev[3] if len(ev) > 3 else ev[0]
                total[label] = total.get(label, 0) + (ev[2] - ev[1])
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]

    def gaps(self, device: int) -> List[Tuple[int, int]]:
        """Idle stretches of ``device`` inside the window."""
        edges = [self.start] + [t for iv in self.busy(device) for t in iv]
        edges.append(self.end)
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def host_label(self, gap: Tuple[int, int]) -> str:
        """What the host was doing in ``gap``: the shortest host event that
        covers ``COVER`` of it, else the one that covers most of it."""
        s, e = gap
        span = max(e - s, 1)
        best, best_cover, inner = "no host event", 0, None
        for name, hs, he in self.host:
            if name == WINDOW_SPAN:
                continue
            cover = min(e, he) - max(s, hs)
            if cover <= 0:
                continue
            if cover >= COVER * span and (inner is None
                                          or he - hs < inner[1]):
                inner = (name, he - hs)
            if cover > best_cover:
                best, best_cover = name, cover
        return inner[0] if inner else best

    def idle_gaps(self, n: int) -> List[list]:
        every = [(e - s, (s, e)) for d in self.devices for s, e in
                 self.gaps(d)]
        every.sort(key=lambda g: -g[0])
        return [[self.host_label(g), ns / 1e9] for ns, g in every[:n]]


def from_events(devices: Dict[int, List[Event]], host: List[Event]
                ) -> Trace:
    """A ``Trace`` over the benchmark's window span, or else over every
    event given; device operations are clipped to it."""
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if spans:
        start, end = spans[0]
    else:
        points = [t for evs in list(devices.values()) + [host]
                  for ev in evs for t in ev[1:3]]
        start, end = min(points), max(points)
    clipped = {d: [(ev[0], max(ev[1], start), min(ev[2], end)) + tuple(ev[3:])
                   for ev in ops if ev[2] > start and ev[1] < end]
               for d, ops in devices.items()}
    return Trace(clipped, host, start, end)


#: characters of an operation's HLO text kept as its label in a breakdown
LABEL_CHARS = 160


def _op(event) -> Event:
    """A device operation: the TPU trace names it by its HLO text,
    ``%nsimplex_zen_topk.1 = (f32[...]) custom-call(...)``; the name is the
    instruction's (``nsimplex_zen_topk.1``), the label the text's head."""
    text = event.name
    name = text[1:].split(" ", 1)[0] if text.startswith("%") else text
    start = int(event.start_ns)
    return (name, start, start + int(event.duration_ns), text[:LABEL_CHARS])


def _device_id(plane_name: str):
    # "/device:TPU:0" (possibly with a suffix after a space)
    tail = plane_name.split()[0].rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def load(trace_dir: str, device_ids: Sequence[int]) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = _device_id(plane.name)
            if dev not in device_ids:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(dev, []).extend(
                        _op(e) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return from_events(devices, host)
