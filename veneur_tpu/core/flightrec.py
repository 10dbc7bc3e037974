"""The server's span record: what ran when, inside a flush and beside it.

One ``Recorder`` per ``Server``, handed to its workers (and through them
to the device guard): a bounded ring of closed spans, always on. A span
is ``(id, name, t_start, t_end, parent, flush ordinal, attrs)``:

* ``t_*`` are ``time.time()``, the clock the ticker, the sinks and any
  profiler anchor already use, so a span sits on a device trace's
  timeline with nothing but the anchor's offset;
* ``parent`` is the span open on the same thread when this one opened
  (a thread that works for another's span passes ``parent=``);
* the flush ordinal is ``Server.flush_count`` for the spans of a flush
  and, for the ingest side (micro-folds, series adoption), the ordinal
  of the flush that will close their epoch; a child inherits its
  parent's unless told otherwise;
* ``attrs["cpu_s"]`` is what the span's thread spent on a core between
  the two times (``time.thread_time()``): the span's length less it, in
  a span that is not ``wait: true``, is time the thread stood still for
  the interpreter, a lock or a page.

A collection of the cycle collector that lands on a thread with a span
open is a ``gc`` span under that span (attrs ``generation``,
``collected``), from ``gc.callbacks``, if it is a full one or lasts a
millisecond (``GC_SPAN_S``): the young generation's ordinary passes,
a few an interval of 0.2-0.5 ms and seven thousand in a flush that
compiles, would be the ring. One that lands on a thread with no span
open (a thread the program did not start) leaves none either, and shows
in the other threads' spans as length their ``cpu_s`` lacks.

Each span also opens a ``jax.profiler.TraceAnnotation`` of its name, so
a profile shows the same spans beside the device lines; with no trace
running that is one call into a disabled TraceMe. The record feeds
``Server.last_flush_phases`` (its keys are sums of these spans, and its
``spans`` key is ``Recorder.of_flush``) and nothing else: it does not
rejoin the span pipeline, where 200 self-spans an interval would be
traffic the operator never sent.
"""

from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
import weakref
from typing import Optional

import jax

#: closed spans kept: some twenty intervals of a busy single-worker server
RING = 8192
#: a collection below generation 2 is a span from this length on
GC_SPAN_S = 0.001


class Span:
    """An open or closed span; the context manager ``Recorder.span``
    returns. ``attrs`` may be added to until the span closes, and by
    whoever kept the span until its flush's record is handed on
    (``of_flush`` copies them then: Server._flush_publish)."""

    __slots__ = ("rec", "id", "name", "t0", "t1", "parent", "flush",
                 "attrs", "_ann", "_cpu0")

    def __init__(self, rec: "Recorder", name: str, parent, flush,
                 attrs: dict) -> None:
        self.rec = rec
        self.id = 0
        self.name = name
        self.t0 = self.t1 = 0.0
        self.parent = parent
        self.flush = flush
        self.attrs = attrs
        self._ann = None
        self._cpu0 = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        rec = self.rec
        stack = rec._stack()
        if stack:
            top = stack[-1]
            if self.parent is None:
                self.parent = top.id
            if self.flush is None:
                self.flush = top.flush
        self.id = next(rec._ids)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.time()
        self._cpu0 = time.thread_time()
        return self

    def __exit__(self, etype, exc, tb) -> bool:
        # a span closes on the thread that opened it: one thread's
        # clock, read inside the span's two times
        self.attrs["cpu_s"] = time.thread_time() - self._cpu0
        self.t1 = time.time()
        self._ann.__exit__(etype, exc, tb)
        self._ann = None
        if etype is not None:
            self.attrs["error"] = etype.__name__
        stack = self.rec._stack()
        # innermost first
        while stack and stack.pop() is not self:
            pass
        self.rec._ring.append(self)  # deque.append is atomic
        return False

    def as_list(self) -> list:
        """JSON types only: [id, name, t_start, t_end, parent, flush
        ordinal, attrs]."""
        return [self.id, self.name, self.t0, self.t1, self.parent,
                self.flush, dict(self.attrs)]


class Recorder:
    def __init__(self, capacity: int = RING) -> None:
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._tls = threading.local()
        # the collector calls every entry of gc.callbacks on the thread
        # whose allocation set it off; the entry holds this record
        # weakly and goes when the record does
        ref = weakref.ref(self)

        def on_gc(phase: str, info: dict) -> None:
            rec = ref()
            if rec is not None:
                rec._gc(phase, info)

        gc.callbacks.append(on_gc)
        weakref.finalize(self, gc.callbacks.remove, on_gc).atexit = False

    def _gc(self, phase: str, info: dict) -> None:
        """A collection's start or stop, on the thread it runs on: with
        a span open there, the pair becomes a closed ``gc`` span under
        it. Nothing is opened: no annotation, nothing on the stack."""
        stack = self._stack()
        if not stack:
            return
        if phase == "start":
            self._tls.gc0 = (time.time(), time.thread_time())
            return
        t0 = getattr(self._tls, "gc0", None)
        if t0 is None:
            return
        self._tls.gc0 = None
        t1 = time.time()
        if info["generation"] < 2 and t1 - t0[0] < GC_SPAN_S:
            return
        top = stack[-1]
        sp = Span(self, "gc", top.id, top.flush, {
            "generation": info["generation"], "collected": info["collected"],
            "cpu_s": time.thread_time() - t0[1]})
        sp.id = next(self._ids)
        sp.t0, sp.t1 = t0[0], t1
        self._ring.append(sp)  # closed: it was never on the stack

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, *, parent: Optional[Span] = None,
             flush: Optional[int] = None, **attrs) -> Span:
        """``with rec.span("extract.readback", wait=True): ...``

        ``parent``: the span this one works for, where that span is open
        on another thread (a sink's thread under emit.sinks)."""
        if parent is not None:
            if flush is None:
                flush = parent.flush
            parent = parent.id
        return Span(self, name, parent, flush, attrs)

    def current(self) -> Optional[Span]:
        """The innermost span open on this thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    def add(self, key: str, n) -> None:
        """Add ``n`` to attr ``key`` of this thread's innermost span."""
        cur = self.current()
        if cur is not None:
            cur.attrs[key] = cur.attrs.get(key, 0) + n

    def closed(self) -> list:
        """The ring, oldest first (a copy)."""
        return list(self._ring)

    def of_flush(self, ordinal: int) -> list:
        """The closed spans that bear this flush ordinal, in the order
        they closed, as plain lists (``Span.as_list``)."""
        return [s.as_list() for s in list(self._ring) if s.flush == ordinal]

    def last(self, name: str) -> Optional[Span]:
        """The newest closed span of that name."""
        for s in reversed(list(self._ring)):
            if s.name == name:
                return s
        return None
