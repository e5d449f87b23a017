"""Host spans of the delivery layer, on the profiler's clock.

A span is a named interval of host time with an optional window id (the
lease ``lo`` of the window it worked on), so that the spans one window
leaves on the producer thread and on the consumer thread can be joined.

Spans record only while a JAX profiler session is active
(``jax.profiler.start_trace`` ... ``stop_trace``); otherwise ``span``
returns a shared no-op after one probe of the profiler.  While a
session is active each span

  * opens a ``jax.profiler.TraceAnnotation`` of its name, so it lands in
    the ``.xplane.pb`` on the host plane, beside the device's operations;
  * appends a :class:`Span` to a bounded in-memory ring, with start and
    end from ``time.time_ns()``: the profiler's clock (an xplane event's
    offset plus the session's ``profile_start_time``).

``recorded()`` returns the ring and ``clear()`` empties it; a reader
takes the spans of a traced window after the session has ended.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, List, NamedTuple, Optional

from jax import profiler as _profiler

#: spans the ring holds; the delivery layer records five a window
CAPACITY = 1 << 16

# ``_is_enabled()`` is True while a profiler session is active (one C call).
# Where this JAX lacks the probe, spans never record and ``PROBE`` is
# False, which the tests refuse.
try:
    from jax._src.lib import _profiler as _xla_profiler
    _is_enabled = _xla_profiler.TraceMe.is_enabled
    PROBE = True
except (ImportError, AttributeError):
    def _is_enabled() -> bool:
        return False
    PROBE = False


class Span(NamedTuple):
    """One recorded span; ``note`` is what the code inside set, if any."""
    name: str
    window: Optional[int]
    thread: str
    start_ns: int
    end_ns: int
    note: Optional[str] = None


class _Ring:
    """Spans in arrival order, the oldest pushed out when full."""

    def __init__(self, capacity: int) -> None:
        self.lock = threading.Lock()
        self.spans: "collections.deque[Span]" = collections.deque(
            maxlen=capacity)
        self.dropped = 0

    def add(self, s: Span) -> None:
        with self.lock:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.spans.append(s)


_ring = _Ring(CAPACITY)


class _Active:
    """A span being recorded; ``window`` and ``note`` may be set inside."""
    __slots__ = ("name", "window", "note", "_start", "_annotation")
    recording = True

    def __init__(self, name: str, window: Optional[int]) -> None:
        self.name = name
        self.window = window
        self.note: Optional[str] = None

    def __enter__(self) -> "_Active":
        self._start = time.time_ns()
        self._annotation = _profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._annotation.__exit__(*exc)
        _ring.add(Span(self.name, self.window,
                       threading.current_thread().name, self._start,
                       time.time_ns(), self.note))


class _Off:
    """The shared no-op span: what is set on it is never read."""
    __slots__ = ("window", "note")
    recording = False

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, typ: Any, value: Any, tb: Any) -> None:
        return None


_OFF = _Off()


def span(name: str, window: Optional[int] = None, *,
         _on: Callable[[], bool] = _is_enabled, _off: _Off = _OFF):
    """Context manager recording ``name`` while a profiler session is on.

    The object it yields takes ``window`` (and ``note``) after entry, for
    code that learns its window inside the span; its ``recording`` says
    whether anything will be kept, so that work done only for the note
    can be skipped.

    Example:
        >>> from repro.runtime import spans
        >>> with spans.span("docs.demo", window=0) as sp:
        ...     sp.window = 64           # learnt inside the span
        >>> sp.recording                 # no profiler session: a no-op
        False
    """
    # _on and _off are bound once, here: the off path runs a few times a
    # window and is kept to one probe and no global lookups
    if _on():
        return _Active(name, window)
    return _off


def recorded() -> List[Span]:
    """The spans in the ring, oldest first: those recorded while a
    profiler session was active since the last ``clear``."""
    with _ring.lock:
        return list(_ring.spans)


def dropped() -> int:
    """Spans pushed out of the full ring since the last ``clear``."""
    return _ring.dropped


def clear() -> None:
    """Empty the ring and zero its count of dropped spans, before the
    profiler session whose spans a reader will take."""
    with _ring.lock:
        _ring.spans.clear()
        _ring.dropped = 0
