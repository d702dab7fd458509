"""Host spans on the profiler's clock, and a count of programs compiled.

``record(name)`` marks a stretch of host code. It always opens a
``jax.profiler.TraceAnnotation``, so that, while the profiler runs, the span
sits on the profiler's clock beside the device's operations (and costs about
a microsecond while it does not). Inside ``collect(root)`` on the same
thread it also appends a :class:`Span` to the collector's list, with the
index of the span that encloses it. There is no switch: the profiler being
active is the only "on".

``compiles()`` counts the programs this process has compiled or loaded from
the persistent compilation cache since its first call; ``thread_compiles()``
counts those the calling thread compiled or loaded (JAX compiles on the
thread that asks for the program), so that work running at once on other
threads is not counted in it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Iterator, List, Optional, Sequence

import jax

# JAX records this once per program handed to the backend, around
# ``compile_or_get_cached``: a fresh compile and a load from the persistent
# cache alike. Its ``cache_hits`` event fires inside the same call, so
# counting both would count a load twice.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int               # time.perf_counter_ns()
    end_ns: int
    parent: Optional[int]       # index of the enclosing span; None at the root

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_local = threading.local()


@contextlib.contextmanager
def record(name: str) -> Iterator[None]:
    """Mark the body as the span ``name`` (a fixed name: ids go elsewhere)."""
    spans = getattr(_local, "spans", None)
    with jax.profiler.TraceAnnotation(name):
        if spans is None:
            yield
            return
        i = len(spans)
        parent = _local.open[-1] if _local.open else None
        spans.append(None)              # filled in when the span ends
        _local.open.append(i)
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            _local.open.pop()
            spans[i] = Span(name, start, time.perf_counter_ns(), parent)


@contextlib.contextmanager
def collect(root: str) -> Iterator[List[Span]]:
    """Record ``root`` and every span recorded inside it on this thread.
    The yielded list is complete when the block ends; ``root`` is first."""
    outer = getattr(_local, "spans", None), getattr(_local, "open", None)
    _local.spans, _local.open = [], []
    try:
        with record(root):
            yield _local.spans
    finally:
        _local.spans, _local.open = outer


def seconds(spans: Sequence[Span], name: str) -> float:
    """Summed seconds of the spans called ``name`` (0 when there is none)."""
    return sum(s.seconds for s in spans if s.name == name)


_lock = threading.Lock()
_compiled: Optional[int] = None     # None until the listener is installed


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    global _compiled
    if event == COMPILE_EVENT:
        _local.compiled = getattr(_local, "compiled", 0) + 1
        with _lock:
            _compiled += 1


def _listen() -> None:
    """Install the listener on the first call (with ``_lock`` held)."""
    global _compiled
    if _compiled is None:
        _compiled = 0
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)


def compiles() -> int:
    """Programs compiled or loaded by this process since the first call:
    read it before and after a stretch of work and take the difference."""
    with _lock:
        _listen()
        return _compiled


def thread_compiles() -> int:
    """Programs compiled or loaded by the calling thread since the first
    call of either counter: as ``compiles()``, for one thread's work."""
    with _lock:
        _listen()
    return getattr(_local, "compiled", 0)
