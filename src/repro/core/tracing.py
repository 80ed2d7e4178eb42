"""Spans inside the program, on the host's `perf_counter_ns` clock and in the
profiler's own trace.

    with tracing.span("fabric.evaluate", op="evaluate") as sp:
        ...
        sp.set(points=n_sent, hits=n_served)

Recording is on inside `recording()` (or between `start()` and `stop()`) and
while a `jax.profiler` trace collects host events, so the spans show in any
trace an operator records:

    with tracing.recording(), jax.profiler.trace(log_dir):
        ...

Off, `span()` returns one shared null object: it records nothing, enters no
`TraceAnnotation` and `wait()` adds no device sync. On, a span enters
`jax.profiler.TraceAnnotation("repro." + name, **attrs)` and appends a
`Record` when it closes. A span's parent is the innermost span open on its
thread; work handed to another thread passes `parent=current()` from the
handing thread, so every span of one fabric call shares its root. While
recording, every backend compile becomes a `jax.compile` span on the
compiling thread, under the span open there.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

from repro.analysis.races import named_lock

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the newest records kept; older ones are dropped
MAX_RECORDS = 1 << 20


@dataclass
class Record:
    """One closed span. `parent` is the `id` of the span that caused it."""
    name: str
    thread: str
    start_ns: int
    end_ns: int
    attrs: dict = field(default_factory=dict)
    id: int = 0
    parent: int | None = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_lock = named_lock("tracing.records")
_records: deque = deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_open = threading.local()  # .stack: this thread's open spans, innermost last
_explicit = 0  # depth of recording() / start()
_listening = False
_profiling = jax.profiler.TraceAnnotation.is_enabled


def is_recording() -> bool:
    return _explicit > 0 or _profiling()


def _stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def current() -> int | None:
    """The id of the innermost span open on this thread, or None."""
    stack = _stack()
    return stack[-1].id if stack else None


def _append(rec: Record) -> None:
    with _lock:
        _records.append(rec)


class _NullSpan:
    __slots__ = ()
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()
_INNERMOST = object()


class _Span:
    __slots__ = ("name", "attrs", "parent", "id", "_start", "_annotation")

    def __init__(self, name: str, parent, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.parent = parent

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work is under way."""
        self.attrs.update(attrs)

    def __enter__(self):
        stack = _stack()
        if self.parent is _INNERMOST:
            self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation("repro." + self.name, **self.attrs)
        self._annotation.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _stack().remove(self)
        _append(Record(self.name, threading.current_thread().name, self._start, end,
                       self.attrs, self.id, self.parent))
        return False


def span(name: str, parent=_INNERMOST, **attrs):
    """A span named `name` while recording, else the shared null span.
    `parent` defaults to the innermost span open on this thread."""
    if not is_recording():
        return NULL_SPAN
    _listen()
    return _Span(name, parent, attrs)


def wait(x):
    """`x`, once ready on the device: while recording, a `device.wait` span
    ends when `jax.block_until_ready(x)` returns, so the host copy that
    follows is not counted as waiting. Off, `x` as it is."""
    if not is_recording():
        return x
    with span("device.wait"):
        jax.block_until_ready(x)
    return x


def _on_compile(event: str, duration_secs: float, **kwargs) -> None:
    if event != COMPILE_EVENT or not is_recording():
        return
    end = time.perf_counter_ns()
    _append(Record("jax.compile", threading.current_thread().name,
                   end - int(duration_secs * 1e9), end, dict(kwargs), next(_ids), current()))


def _listen() -> None:
    global _listening
    if _listening:
        return
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _listening = True


def start() -> None:
    global _explicit
    with _lock:
        _explicit += 1
    _listen()


def stop() -> None:
    global _explicit
    with _lock:
        _explicit = max(0, _explicit - 1)


@contextmanager
def recording():
    start()
    try:
        yield
    finally:
        stop()


def records(lo_ns: int | None = None, hi_ns: int | None = None) -> list[Record]:
    """The records kept, those inside [lo_ns, hi_ns] where given."""
    with _lock:
        out = list(_records)
    return [r for r in out
            if (lo_ns is None or r.start_ns >= lo_ns) and (hi_ns is None or r.end_ns <= hi_ns)]


def clear() -> None:
    with _lock:
        _records.clear()
