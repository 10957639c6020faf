"""Program spans: named, nested host intervals around the work the program
does with real bytes (checkpoint save and restore, replication, the training
loop's host side).

    from repro.obs import spans

    with spans.span("ckpt.write", files=4) as s:
        ...
        s.set(bytes=n)

A span is written twice.  The process-wide ``Recorder`` keeps its record
(name, start and end on ``time.perf_counter()``, id, parent id, attributes)
in a bounded ring that drops the oldest record and counts the drop, and
per-name totals (count, seconds, self seconds) that it never drops.  While a
``jax.profiler`` trace runs, the span is also a ``TraceAnnotation`` of the
same name, so it lands on the trace's host plane on the clock of the
device's operations; with no trace running the annotation is a no-op.

The recorder is always on: it costs a few microseconds a span, so spans
belong at boundaries that move bytes, never in a per-tick loop of the
simulator (``PhaseProfiler`` wraps those on demand, in a recorder of its
own).  A span's parent is the innermost span open on the same thread, so a
save run on a background thread keeps its own tree.  A span's self time is
its duration less the durations of its children on its own thread.

Work handed to a pool of threads stays in its caller's tree through
``adopt``: a worker opens its spans under a span open on another thread.

    with spans.span("transport.submit") as s:
        pool.submit(work, s)                # work runs `with spans.adopt(s):`

Such a child carries its parent's id but does not subtract from the parent's
self time, since the two overlap in wall time and several children may run at
once.  Under a trace, a worker's annotations land on its own host thread.

jax is not imported here: the annotation is taken from jax once the process
has loaded it, since a process that never loads jax runs no profiler.
Host-only simulator workers import this module without loading jax.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional

CAPACITY = 16_384


class Span(NamedTuple):
    """One finished span."""
    name: str
    start: float                    # time.perf_counter(), seconds
    end: float
    id: int
    parent: Optional[int]           # id of the enclosing span on its thread,
                                    # or of the span it was adopted under
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Total(NamedTuple):
    """Every finished span of one name, dropped from the ring or not."""
    count: int
    seconds: float
    self_seconds: float


_annotation = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once jax is loaded, else ``None``."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class OpenSpan:
    """The context manager ``Recorder.span`` returns; ``set`` adds
    attributes while it is open."""
    __slots__ = ("_rec", "_stack", "name", "attrs", "id", "parent", "start",
                 "_child_s", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self._rec, self.name, self.attrs = rec, name, attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "OpenSpan":
        rec = self._rec
        try:
            stack = rec._local.stack
        except AttributeError:
            stack = rec._local.stack = []
        self._stack = stack
        self.parent = stack[-1].id if stack else None
        self.id = next(rec._ids)
        self._child_s = 0.0
        stack.append(self)
        ann = _annotation or _annotation_class()
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self._stack
        stack.pop()
        seconds = end - self.start
        if stack:
            stack[-1]._child_s += seconds
        rec, name = self._rec, self.name
        with rec._lock:
            ring = rec._ring
            if len(ring) == ring.maxlen:
                rec.dropped += 1
            ring.append((name, self.start, end, self.id, self.parent,
                         self.attrs))
            t = rec._totals.get(name)
            if t is None:
                rec._totals[name] = [1, seconds, seconds - self._child_s]
            else:
                t[0] += 1
                t[1] += seconds
                t[2] += seconds - self._child_s


class _Adopted:
    """A worker thread's stand-in under ``adopt`` for a span open on another
    thread: it gives the worker's spans their parent id, and the seconds
    they add to its children are counted nowhere."""
    __slots__ = ("id", "_child_s")

    def __init__(self, span_id: int):
        self.id, self._child_s = span_id, 0.0


class Recorder:
    """Spans in a bounded ring plus per-name totals; thread-safe."""

    def __init__(self):
        self._ring: collections.deque = collections.deque(maxlen=CAPACITY)
        self._totals: Dict[str, List[float]] = {}
        self._ids = itertools.count(1)
        self._local = threading.local()     # .stack: this thread's open spans
        self._lock = threading.Lock()
        self.dropped = 0

    def span(self, name: str, **attrs) -> OpenSpan:
        return OpenSpan(self, name, attrs)

    def records(self) -> List[Span]:
        """The spans still in the ring, oldest first."""
        with self._lock:
            ring = list(self._ring)
        return [Span._make(r) for r in ring]

    def totals(self) -> Dict[str, Total]:
        with self._lock:
            return {n: Total(*t) for n, t in self._totals.items()}


RECORDER = Recorder()


def span(name: str, **attrs) -> OpenSpan:
    """A span of the process-wide recorder."""
    return OpenSpan(RECORDER, name, attrs)


def records() -> List[Span]:
    return RECORDER.records()


@contextlib.contextmanager
def adopt(parent: OpenSpan) -> Iterator[None]:
    """Open this thread's spans of ``parent``'s recorder under ``parent``, a
    span open on another thread."""
    local = parent._rec._local
    try:
        stack = local.stack
    except AttributeError:
        stack = local.stack = []
    stack.append(_Adopted(parent.id))
    try:
        yield
    finally:
        stack.pop()


def totals() -> Dict[str, Total]:
    return RECORDER.totals()
