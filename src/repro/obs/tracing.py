"""Spans of the serve stack, on two clocks (tfprof-style).

The Taskflow paper ships a built-in profiler (tfprof, §VI) that records
per-worker task intervals and renders them as an execution timeline; this
module is the serve-stack analogue. :class:`span` is the one way code
marks a span. It has two sinks:

* the **profiler's trace**: every span enters a
  ``jax.profiler.TraceAnnotation`` of its name, so a ``jax.profiler``
  session (``jax.profiler.trace(dir)``) records it on the host plane, on
  the same clock as the device's operations. Outside a session the
  annotation is the profiler's own no-op switch (well under a
  microsecond). JAX is looked up only once something has imported it, so
  the runtime (``core/``, ``pipeline/``) stays importable without it;
* a :class:`Tracer`, when one is passed and enabled: a bounded RING
  BUFFER of completed spans — plain ``(name, track, t_start, t_end,
  args)`` tuples on the ``time.perf_counter`` clock — exported to Chrome
  trace JSON by :mod:`repro.obs.export` (``launch/serve.py --trace``).

Tracks partition the ring buffer's timeline the way tfprof partitions by
worker: one track per decode slot (``"slot3"``) carrying that seat's
request lifecycle spans (queued → admitted → prefill_window → decode →
stalled → retired, recorded after the fact with :meth:`Tracer.add`), one
``"engine"`` track carrying the engine's ``serve.*`` phase spans, and one
``"lineN"`` track per pipeline line with the ``pipe.<stage>`` body spans.
An *instant* is a span with ``t_end == t_start`` (exported as a Chrome
trace instant event) — used for point events like ``retired`` and
``preempted``.

Design constraints (the serve hot loop opens about ten spans a cycle):

* ``add`` is one lock acquisition + one list write; the buffer never
  grows past ``capacity`` — old spans are overwritten oldest-first and
  counted in :attr:`dropped` (a trace that wrapped says so instead of
  silently lying);
* a disabled or absent tracer costs the span an attribute check, so a
  span with no tracer and no profiler session costs the annotation's
  enter and exit and two clock reads.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Span", "Tracer", "TRACK_ENGINE", "span"]

#: the engine-cycle track name (one per engine; slot tracks are "slotN")
TRACK_ENGINE = "engine"

#: (name, track, t_start, t_end, args) — t_* on the perf_counter clock
Span = Tuple[str, str, float, float, Optional[Dict[str, Any]]]


class Tracer:
    """Thread-safe bounded span recorder (see module docstring).

    Parameters
    ----------
    capacity:
        ring-buffer size in spans; the newest ``capacity`` spans are kept
        and :attr:`dropped` counts overwritten ones.
    enabled:
        ``False`` makes every recording method a near-no-op (checked
        before the lock). Flip :attr:`enabled` at will — recording sites
        re-check it on every call.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True) -> None:
        if capacity < 1:
            raise ValueError("tracer capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        #: perf_counter origin — export rebases timestamps onto it
        self.t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._buf: List[Span] = []
        self._write = 0          # overwrite cursor once the buffer is full
        self.dropped = 0         # spans overwritten by ring wrap

    # -------------------------------------------------------------- recording
    def add(self, name: str, track: str, t_start: float, t_end: float,
            args: Optional[Dict[str, Any]] = None) -> None:
        """Record one completed span. ``t_end == t_start`` is an instant."""
        if not self.enabled:
            return
        span = (name, track, t_start, t_end, args)
        with self._lock:
            if len(self._buf) < self.capacity:
                self._buf.append(span)
            else:
                self._buf[self._write] = span
                self._write = (self._write + 1) % self.capacity
                self.dropped += 1

    def instant(self, name: str, track: str, t: Optional[float] = None,
                args: Optional[Dict[str, Any]] = None) -> None:
        """Record a point event (a zero-duration span)."""
        if not self.enabled:
            return
        if t is None:
            t = time.perf_counter()
        self.add(name, track, t, t, args)

    # ---------------------------------------------------------------- reading
    def spans(self) -> List[Span]:
        """A chronological (oldest-first) copy of the buffered spans."""
        with self._lock:
            return self._buf[self._write:] + self._buf[:self._write]

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def clear(self) -> None:
        """Drop every buffered span (the perf_counter origin is kept, so
        spans recorded before and after a clear stay on one clock)."""
        with self._lock:
            self._buf = []
            self._write = 0
            self.dropped = 0


#: ``jax.profiler.TraceAnnotation``, found once JAX has been imported
_annotation = None


def _annotation_type():
    """The profiler's annotation type, or None while JAX is not imported
    (no profiler session can be on then) or not installed."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            return None
        _annotation = TraceAnnotation
    return _annotation


class span:
    """Mark the wrapped block as one span named ``name``, in both sinks
    (see the module docstring)::

        with span("serve.sync", tracer):
            toks = np.asarray(toks)

    ``args`` ride on both sinks: as the annotation's metadata and as the
    ring-buffer span's args. :meth:`annotate` adds args known only inside
    the block. ``t0``/``t1`` hold the block's ``perf_counter`` bounds
    whatever the sinks, so callers time the block from the span itself.
    """

    __slots__ = ("name", "track", "args", "t0", "t1", "_tracer", "_ann")

    def __init__(self, name: str, tracer: Optional[Tracer] = None,
                 track: str = TRACK_ENGINE, **args: Any) -> None:
        self.name = name
        self.track = track
        self.args = args
        self.t0 = self.t1 = 0.0
        self._tracer = tracer
        self._ann = None

    def annotate(self, **args: Any) -> None:
        """Add args to the open span (both sinks)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "span":
        ann = _annotation_type()
        if ann is not None:
            self._ann = ann(self.name, **self.args)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._tracer is not None:
            self._tracer.add(self.name, self.track, self.t0, self.t1,
                             self.args or None)
