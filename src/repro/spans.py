"""Spans and compile counters of the program's host path, kept in memory.

``span(name, **attrs)`` times a block of host code. Each span

* opens a ``jax.profiler.TraceAnnotation`` of the same name (its attrs
  become the event's stats), so that under the profiler it lands on the
  host plane, on the clock of the device planes;
* is recorded, once it closes, in a bounded in-process ring
  (:data:`RING_SIZE` records, oldest dropped first) as a :class:`Span`:
  its id, its parent's and its root's ids, name, start and end on
  ``time.perf_counter_ns``, attrs and counters;
* is marked ``failed`` when its body raised (the exception propagates).

The root of a span is the outermost span open in its thread when it
opened, so all spans of one ``sweep.run`` call share the id of its
``fg.sweep`` span. ``recent(name)`` returns the completed records, newest
last.

Counters come from one ``jax.monitoring`` listener, registered when this
module is imported: the count and seconds of each event in
:data:`COUNTERS` (tracing, lowering, backend compilation and persistent
cache retrieval) are added to the innermost span open in the calling
thread, so a retrace or recompile shows on the step that paid for it.
Trace events of nested ``jit``s nest: an outer function's trace time
includes its inner functions'.

The ring is always on: a span costs two clock reads, a deque append and a
``TraceAnnotation``, which does nothing while no profiler runs. Importing
this module initializes no JAX backend. The program's spans are named
``fg.*``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time

import jax

__all__ = ["COUNTERS", "RING_SIZE", "Span", "compile_seconds", "recent",
           "self_ns", "span", "tree"]

#: JAX monitoring events counted on spans, by the short name a span's
#: ``counters`` uses.
COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
RING_SIZE = 4096


@dataclasses.dataclass(eq=False)
class Span:
    """One span: open while its block runs, then a record in the ring.
    ``counters`` maps a short name of :data:`COUNTERS` to ``[count,
    seconds]``."""

    id: int
    parent: int | None
    root: int
    name: str
    t0_ns: int
    t1_ns: int = 0
    attrs: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    failed: bool = False

    @property
    def ns(self) -> int:
        return self.t1_ns - self.t0_ns


_RING: collections.deque = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_open = threading.local()


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the block as span ``name`` (see the module doc); yields the
    open :class:`Span`, whose ``attrs`` the block may extend."""
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1] if stack else None
    with jax.profiler.TraceAnnotation(name, **attrs):
        s = Span(id=sid, parent=parent.id if parent else None,
                 root=parent.root if parent else sid, name=name,
                 t0_ns=time.perf_counter_ns(), attrs=dict(attrs))
        stack.append(s)
        try:
            yield s
        except BaseException:
            s.failed = True
            raise
        finally:
            s.t1_ns = time.perf_counter_ns()
            stack.pop()
            _RING.append(s)


def _on_event(event: str, seconds: float, **_) -> None:
    key = COUNTERS.get(event)
    stack = _stack()
    if key is None or not stack:
        return
    c = stack[-1].counters.setdefault(key, [0, 0.0])
    c[0] += 1
    c[1] += seconds


jax.monitoring.register_event_duration_secs_listener(_on_event)


def recent(name: str | None = None) -> list[Span]:
    """Completed spans in the ring (of ``name`` only, if given), newest
    last."""
    return [s for s in list(_RING) if name is None or s.name == name]


def tree(root: Span) -> list[Span]:
    """The completed spans in the ring whose root is ``root``, itself
    included."""
    return [s for s in list(_RING) if s.root == root.id]


def self_ns(s: Span) -> int:
    """The span's duration less the time its direct children in the ring
    cover (children of one span do not overlap: a thread runs one at a
    time)."""
    return s.ns - sum(c.ns for c in list(_RING) if c.parent == s.id)


def compile_seconds(spans) -> float:
    """Seconds of every :data:`COUNTERS` event recorded on ``spans``."""
    return sum(sec for s in spans for _, sec in s.counters.values())
