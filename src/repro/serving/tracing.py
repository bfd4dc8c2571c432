"""Spans and compile counts of the serving path.

The serving path names its own steps in the profiler's trace: each step
runs inside :func:`span`, a thin wrapper over
``jax.profiler.TraceAnnotation``, so the spans land in the trace that
``jax.profiler.start_trace`` collects, on its clock, beside the device's
operations. The profiler is the switch: with no trace running a span
formats no argument and records nothing, and nothing here writes a file.

One identifier links a request to the dispatch that served it: ``zen.submit``
carries the request's sequence number (``QueryHandle.request_id``) and
``zen.dispatch`` the list of the numbers it served.

  zen.submit    MicroBatchScheduler.submit (caller's thread): request, rows
  zen.tick      a scheduler tick that found pending rows: pending
  zen.dispatch  one dispatch, from stacking its rows to resolving its
                handles: dispatch, requests, rows, bucket, width
  zen.project   ZenServer._query_block: the nSimplex projection
  zen.search    _query_block: IVF probe or flat scan; index = ivf / flat /
                sharded
  zen.rerank    _query_block: exact re-rank of the candidates
  zen.map_ids   _query_block: row positions to external ids, the pad to the
                output width
  zen.fetch     the dispatch's wait for its results on the host
  zen.resolve   filling handles, cache entries and stats under the lock

The profiler encodes a span's arguments into its name as ``name#k=v#``, so
a reader matches on the part before ``#``.

Besides, each thread keeps the stack of its open ``zen.*`` spans whether or
not a trace runs, and :func:`count_compiles` registers one
``jax.monitoring`` listener that counts the executables JAX builds under
the innermost open span of the building thread: which step recompiled.
"""
from __future__ import annotations

import collections
import contextlib
import threading
from typing import Dict, List

import jax
from jax.profiler import TraceAnnotation

SUBMIT = "zen.submit"
TICK = "zen.tick"
DISPATCH = "zen.dispatch"
PROJECT = "zen.project"
SEARCH = "zen.search"
RERANK = "zen.rerank"
MAP_IDS = "zen.map_ids"
FETCH = "zen.fetch"
RESOLVE = "zen.resolve"

#: the step a compile outside every ``zen.*`` span is counted under
NO_SPAN = "none"

#: the event JAX records around each executable it builds (a compile, or a
#: load from the persistent compilation cache)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()


def _stack() -> List[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def current() -> str:
    """The innermost open ``zen.*`` span of this thread, or ``"none"``."""
    stack = _stack()
    return stack[-1] if stack else NO_SPAN


def _arg(value):
    # the profiler splits arguments at commas outside brackets
    if isinstance(value, (list, tuple)):
        return "[" + ",".join(str(v) for v in value) + "]"
    return value


@contextlib.contextmanager
def span(name: str, **args):
    """Mark the enclosed code as step ``name`` of the serving path."""
    stack = _stack()
    stack.append(name)
    try:
        if TraceAnnotation.is_enabled():
            with TraceAnnotation(name, **{k: _arg(v)
                                          for k, v in args.items()}):
                yield
        else:
            yield
    finally:
        stack.pop()


class _CompileCounter:
    """Executables built in this process, by the step that built them."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = collections.Counter()
        self._registered = False

    def register(self) -> None:
        with self._lock:
            if not self._registered:
                jax.monitoring.register_event_duration_secs_listener(
                    self._event)
                self._registered = True

    def _event(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            step = current()
            with self._lock:
                self._counts[step] += 1

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


_compiles = _CompileCounter()


def count_compiles() -> None:
    """Start counting compiles by step (once per process; idempotent)."""
    _compiles.register()


def compiles() -> Dict[str, int]:
    """Executables built since :func:`count_compiles` was first called,
    keyed by the innermost open ``zen.*`` span of the building thread
    (``"none"`` outside every span). The count is the process's, shared by
    every server in it."""
    return _compiles.counts()
