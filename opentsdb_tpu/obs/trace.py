"""Per-query trace spans: a lightweight span tree threaded through the
executor, planner, and storage fan-out.

Activation model (the faultpoints cost discipline): a module-level
active-trace counter gates every hook — with no trace active anywhere
in the process, ``span()`` / ``current_span()`` are one global integer
check and return a shared no-op. A trace is activated around one
query's execution (``activate``); the contextvar keeps concurrent
queries' spans separate even though they share one executor and one
thread pool.

Span durations are wall-clock (``perf_counter``) milliseconds; a
span's start is one ``time.time_ns()`` reading taken at enter, so a
tree can be laid beside a profiler trace or another process's tree.
Beside the wall time a span keeps ``cpu_ms``, the time its thread was
on a CPU between the same two points (``time.thread_time_ns()``), so
``ms - cpu_ms`` is the time the thread was not running: waiting for
the interpreter lock, the device, another lock or I/O. Work a span
hands to another thread is in its ``ms`` and not in its ``cpu_ms``.
The tree serializes as::

    {"name": ..., "t0": 1790550860.123457, "ms": 12.3, "cpu_ms": 4.5,
     "tags": {...}, "spans": [children]}

(``t0``: epoch seconds, microsecond precision.) A span that closes
also adds its two times to the registry counters
``query.span.wall_ms{span=<name>}`` and ``query.span.cpu_ms{span=
<name>}``, so ``/stats`` holds the pair summed over every traced
request.

A sub-query the server hands from its event loop to a pool thread
gets two more children on its root (``Hops``): ``<prefix>.queue``,
from the hand-over until a pool thread begins it, and
``<prefix>.resume``, from the pool thread's return until the
coroutine runs again (``http.q.queue`` / ``http.q.resume`` for
``/q``). Both are waits (``cpu_ms`` 0) and both lie OUTSIDE the root's
own interval, before its start and after its end: the root's ``ms``
is what it was without them, and the children of a root no longer
sum to at most its ``ms``.

While a span is open it is also a ``jax.profiler.TraceAnnotation`` of
the same name carrying the trace's ``trace_id``, so a profiler session
shows the program's spans on its host timeline, on the clock the
device's operations are on. ``timed()`` does the same for threads that
serve no request (the checkpoint timer, the event loop): it observes a
registry timer, adds the block's thread CPU time to the counter
``<name>.cpu_ms`` of the same tags, and is an annotation of the
timer's name. The profiler is imported at the first span or
``timed()`` block, never before.

Storage fan-out gets ``timed_iter``: the sharded store's per-shard
scan iterators are interleaved by the heap merge, so each shard's span
accumulates only the time spent pulling from THAT shard and attaches
to the parent when the iterator is exhausted (the pull times are
disjoint, so shard spans always sum to <= their parent).

Armed ``delay``-mode faultpoints record a ``fault.delay`` child span
(site tag) under whatever span is current when they fire — how a
deterministic test proves exactly one stage stretched.
"""

from __future__ import annotations

import contextvars
import threading
import time
from contextlib import contextmanager

from opentsdb_tpu.obs.registry import METRICS

_ACTIVE = 0                     # process-wide count of active traces
_ACTIVE_LOCK = threading.Lock()
_ANNOTATION = None              # jax.profiler.TraceAnnotation, once used


def new_trace_id() -> str:
    """16 hex chars of urandom — collision-safe across processes
    (os.urandom, not random: child processes fork with copied PRNG
    state and routers/replicas must never mint the same id)."""
    import os
    return os.urandom(8).hex()


_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "opentsdb_tpu_trace_span", default=None)
_TRACE_ID: contextvars.ContextVar = contextvars.ContextVar(
    "opentsdb_tpu_trace_id", default=None)


def _annotation(name: str, **stats):
    """A profiler annotation of this name: outside a profiler session
    entering one checks a flag and no more. Imported at the first use,
    so that a process that opens no span imports no profiler, and kept:
    the import statement alone costs 14 us a time (jax.profiler
    resolves its names lazily)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name, **stats)


# The two counters a closed span adds to, by span name: looked up in
# the registry once a name, not once a span.
_SPAN_COUNTERS: dict[str, tuple] = {}


class Span:
    __slots__ = ("name", "tags", "t0", "c0", "wall_ns", "ms", "cpu_ms",
                 "children")

    def __init__(self, name: str, tags: dict | None = None) -> None:
        # Not started: whoever opens the span calls start(), so that a
        # span costs one reading of each clock at either end.
        self.name = name
        self.tags = tags if tags is not None else {}
        self.wall_ns = 0
        self.ms = self.cpu_ms = 0.0
        self.children: list[Span] = []

    def start(self) -> None:
        self.wall_ns = time.time_ns()
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time_ns()

    def stop(self) -> None:
        """Wall and thread CPU time since ``start()``, which the same
        thread called."""
        self.ms = (time.perf_counter() - self.t0) * 1000.0
        self.cpu_ms = (time.thread_time_ns() - self.c0) / 1e6

    def count(self) -> None:
        """Add the closed span to ``query.span.wall_ms`` / ``.cpu_ms``."""
        pair = _SPAN_COUNTERS.get(self.name)
        if pair is None:
            tags = {"span": self.name}
            pair = _SPAN_COUNTERS[self.name] = (
                METRICS.counter("query.span.wall_ms", tags),
                METRICS.counter("query.span.cpu_ms", tags))
        pair[0].inc(self.ms)
        pair[1].inc(self.cpu_ms)

    @classmethod
    def closed(cls, name: str, tags: dict | None, wall_ns: int,
               ms: float, cpu_ms: float) -> "Span":
        """A span whose times were taken elsewhere, counted."""
        sp = cls(name, tags)
        sp.wall_ns, sp.ms, sp.cpu_ms = wall_ns, ms, cpu_ms
        sp.count()
        return sp

    def to_dict(self) -> dict:
        d = {"name": self.name, "t0": round(self.wall_ns / 1e9, 6),
             "ms": round(self.ms, 3), "cpu_ms": round(self.cpu_ms, 3)}
        if self.tags:
            d["tags"] = self.tags
        if self.children:
            d["spans"] = [c.to_dict() for c in self.children]
        return d


class Trace:
    """One query's span tree; ``root.ms`` is set by ``activate``.

    ``trace_id`` is the cross-process correlation handle: the router
    mints one per front-door request and passes it to every replica
    hop (``?trace_parent=``), so the hop's ring record on the replica
    and the assembled tree on the router carry the SAME id — one grep
    finds a request's whole fan-out. Locally-originated traces mint
    their own."""

    def __init__(self, label: str, tags: dict | None = None,
                 trace_id: str | None = None) -> None:
        self.root = Span("query", dict(tags or ()))
        self.root.tags["q"] = label
        self.trace_id = trace_id or new_trace_id()

    @property
    def total_ms(self) -> float:
        return self.root.ms

    def to_dict(self) -> dict:
        return self.root.to_dict()


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _SpanCtx:
    __slots__ = ("span", "_token", "_ann")

    def __init__(self, name: str, tags: dict | None) -> None:
        self.span = Span(name, tags)

    def __enter__(self) -> Span:
        self._ann = _annotation(self.span.name, trace_id=_TRACE_ID.get())
        self._ann.__enter__()
        self._token = _CURRENT.set(self.span)
        self.span.start()
        return self.span

    def __exit__(self, *exc) -> None:
        sp = self.span
        sp.stop()
        sp.count()
        _CURRENT.reset(self._token)
        parent = _CURRENT.get()
        if parent is not None:
            parent.children.append(sp)
        self._ann.__exit__(*exc)


def span(name: str, **tags):
    """Context manager for one timed child span of the current span.
    No-op (yields None) when no trace is active on this thread."""
    if not _ACTIVE or _CURRENT.get() is None:
        return _NOOP
    return _SpanCtx(name, tags or None)


@contextmanager
def timed(name: str, **tags):
    """Context manager (or decorator) for a phase that belongs to no
    query: observes the registry timer ``name`` (with these tags), adds
    the thread CPU time of the block to the counter ``<name>.cpu_ms``
    and, for as long as it runs, is a profiler annotation of that
    name."""
    key = tags or None
    with _annotation(name, **tags), METRICS.timer(name, key).time(
            METRICS.counter(name + ".cpu_ms", key)):
        yield


def current_span() -> Span | None:
    """The innermost active span on this thread, None when untraced."""
    if not _ACTIVE:
        return None
    return _CURRENT.get()


@contextmanager
def activate(trace: Trace):
    """Run a block with ``trace`` active: its root becomes the current
    span on this thread and its total wall time is recorded."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE += 1
    token = _CURRENT.set(trace.root)
    id_token = _TRACE_ID.set(trace.trace_id)
    trace.root.start()
    try:
        with _annotation(trace.root.name, trace_id=trace.trace_id):
            yield trace
    finally:
        trace.root.stop()
        trace.root.count()
        _TRACE_ID.reset(id_token)
        _CURRENT.reset(token)
        with _ACTIVE_LOCK:
            _ACTIVE -= 1


def timed_iter(it, parent: Span, name: str, tags: dict | None = None):
    """Wrap an iterator so the time spent pulling from it accumulates
    into one child span of ``parent``, attached when the iterator is
    exhausted (or closed). Used for the sharded store's fan-out, where
    the heap merge interleaves shard iterators."""
    total = 0.0
    cpu_ns = 0
    rows = 0
    wall_ns = time.time_ns()
    try:
        while True:
            t0 = time.perf_counter()
            c0 = time.thread_time_ns()
            try:
                item = next(it)
            except StopIteration:
                break
            finally:
                cpu_ns += time.thread_time_ns() - c0
                total += time.perf_counter() - t0
            rows += 1
            yield item
    finally:
        tags = dict(tags or ())
        tags["rows"] = rows
        parent.children.append(Span.closed(
            name, tags, wall_ns, total * 1000.0, cpu_ns / 1e6))


class Hops:
    """The two waits of a call an event loop hands to a pool thread,
    as children of a root span: made on the loop just before the
    hand-over, ``run`` wraps the call on the pool thread, ``attach`` is
    called on the loop once the coroutine runs again."""

    __slots__ = ("wall_ns", "t_submit", "t_begin", "end_ns", "t_end")

    def __init__(self) -> None:
        self.wall_ns = time.time_ns()
        self.t_submit = time.perf_counter()

    def run(self, fn):
        self.t_begin = time.perf_counter()
        try:
            return fn()
        finally:
            self.end_ns = time.time_ns()
            self.t_end = time.perf_counter()

    def attach(self, root: Span, prefix: str) -> None:
        resumed = time.perf_counter()
        root.children.insert(0, Span.closed(
            prefix + ".queue", None, self.wall_ns,
            (self.t_begin - self.t_submit) * 1000.0, 0.0))
        root.children.append(Span.closed(
            prefix + ".resume", None, self.end_ns,
            (resumed - self.t_end) * 1000.0, 0.0))
