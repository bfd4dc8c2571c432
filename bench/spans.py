"""Arithmetic on the program's own spans in a reduced trace
(``bench/trace.py``), shared by the readers of the served path's host time
and of the device's idle time between dispatches.

The program names its steps with ``jax.profiler.TraceAnnotation``
(``repro.serving.tracing``): one ``zen.dispatch`` span per dispatch, from
stacking its rows to resolving its handles, and inside it a ``zen.fetch``
span while the host waits for the dispatch's results. The profiler may
keep a span's arguments in its name as ``name#k=v#``, so names are matched
on the part before ``#``. A program without these spans gives None.
"""
from __future__ import annotations

from typing import List, Tuple

from bench import trace as trace_lib

DISPATCH = "zen.dispatch"
FETCH = "zen.fetch"


def named(tr, name: str) -> List[Tuple[int, int]]:
    """(start, end) of the host events called ``name``, in order."""
    return sorted((s, e) for n, s, e in tr.host
                  if n.split("#", 1)[0] == name)


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(e - s, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_ms_per_dispatch(tr):
    """Mean over the window's ``zen.dispatch`` spans of the span's length
    less the part its ``zen.fetch`` spans cover, in ms: the host's own work
    for a dispatch, with the wait for the device taken out. A dispatch cut
    by the window's edge is left out."""
    dispatches = [(s, e) for s, e in named(tr, DISPATCH)
                  if tr.start <= s and e <= tr.end]
    if not dispatches:
        return None
    fetches = trace_lib.merge(named(tr, FETCH))
    own = sum((e - s) - _overlap([(s, e)], fetches) for s, e in dispatches)
    return own / len(dispatches) / 1e6


def idle_between_dispatches_percent(tr):
    """Share of the window, in percent, in which the device runs no
    operation and no ``zen.dispatch`` span is open (averaged over the
    devices): idle the dispatches did not cause."""
    dispatches = trace_lib.merge(named(tr, DISPATCH))
    if tr.n_ops == 0 or not dispatches:
        return None
    idle = sum(trace_lib.length(tr.gaps(d)) - _overlap(tr.gaps(d), dispatches)
               for d in tr.devices) / len(tr.devices)
    return 100.0 * idle / (tr.end - tr.start)
