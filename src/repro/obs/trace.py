"""Span-based tracing with Chrome-trace export, on the profiler's clock.

A :func:`Tracer.span` context manager records host-side wall time
(``time.perf_counter``) around a region and appends one record to a
bounded ring buffer.  Every span carries a span id and the id of the
span open around it on the same thread (its parent), so a layer's self
time — its duration minus its children's — can be computed from the
ring.  Export is the Chrome trace-event JSON format (``ph: "X"``
complete events, both ids in ``args``), which loads directly in
Perfetto / ``chrome://tracing`` — one lane per thread, spans nest by
timestamp.

Every span also enters ``jax.profiler.TraceAnnotation(name)``: when a
jax profiler session is active, the span lands on the profiler's host
plane, on the same clock as the device planes, so device idle gaps can
be charged to the program span the host was in.  With no session
attached the annotation costs about a microsecond.  The class is
resolved once per process; without jax (stdlib-only layers) spans
record into the ring alone.

Rules that keep tracing off the hot device path (DESIGN.md §15):

  * Spans never synchronize the device.  A span around a jitted call
    measures HOST dispatch wall time (async dispatch returns before the
    device finishes).  A site that blocks on a device result anyway
    times the block with :meth:`Tracer.waiting`, which adds the blocked
    seconds to the innermost open span's ``wait_s`` attribute; the
    device-to-host copy after the block stays host time.
  * Spans are never emitted from INSIDE jitted code — under a trace
    they would record trace-time once and nothing thereafter.  Every
    instrumented site in kernels/codec/serve/ckpt sits at the host
    dispatch layer for exactly this reason.
  * Loops that run per chunk of work (the Rice coder's chunk loop) put
    one span around the loop and count the chunks in its attributes,
    so the ring holds whole windows of requests.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional

from repro.obs import _state

# a whole benchmark window of the busiest root: a full read of a 4-slice
# container records 19 spans, and one reader completes 600+ reads in 51 s
DEFAULT_CAPACITY = 65536


class SpanRecord(NamedTuple):
    name: str
    cat: str  # subsystem ("kernels", "codec", "serve", "ckpt", "collectives")
    ts_us: float  # start, microseconds since the tracer's origin
    dur_us: float
    tid: int
    args: Dict[str, object]
    span_id: int
    parent_id: Optional[int]  # the span open around it on its thread


_annotation_cls = None


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``; the class is imported on
    the first span of the process, never per call."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except Exception:  # noqa: BLE001 - no jax in stdlib-only layers
            cls = contextlib.nullcontext
        _annotation_cls = cls
    return _annotation_cls(name)


class _Span:
    """One open span: pushed on its thread's stack at enter, recorded at
    exit (also when the region raises)."""

    __slots__ = ("tracer", "name", "cat", "attrs", "span_id", "parent_id",
                 "ann", "t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str, attrs: Dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def __enter__(self) -> Dict[str, object]:
        stack = self.tracer._stack()
        self.span_id = next(self.tracer._ids)
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        tracer = self.tracer
        tracer._stack().pop()
        tracer._append(SpanRecord(
            name=self.name,
            cat=self.cat,
            ts_us=(self.t0 - tracer._origin) * 1e6,
            dur_us=(t1 - self.t0) * 1e6,
            tid=threading.get_ident(),
            args=self.attrs,
            span_id=self.span_id,
            parent_id=self.parent_id,
        ))
        return False


class _Wait:
    """Times a block on the device into the innermost open span."""

    __slots__ = ("tracer", "t0")

    def __init__(self, tracer: "Tracer"):
        self.tracer = tracer

    def __enter__(self) -> None:
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        self.tracer.add_wait(time.perf_counter() - self.t0)
        return False


class Tracer:
    """Bounded ring of completed spans + Chrome-trace export."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._spans: Deque[SpanRecord] = deque(maxlen=capacity)
        self._origin = time.perf_counter()
        self._total = 0
        self._ids = itertools.count(1)
        self._local = threading.local()  # per-thread stack of open spans

    def _stack(self) -> List[_Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _append(self, rec: SpanRecord) -> None:
        with self._lock:
            self._spans.append(rec)
            self._total += 1

    def span(self, name: str, subsystem: str = "", **attrs: object):
        """Context manager recording host wall time for the region.

        ``subsystem`` becomes the Chrome-trace category; ``attrs`` land
        in the record's ``args``.  The ``with`` target is that attrs
        dict, so the region can add attributes it only learns inside
        (counts, ``wait_s``).  Disabled tracing records nothing and
        still yields a dict.
        """
        if not _state.enabled:
            return contextlib.nullcontext(attrs)
        return _Span(self, name, subsystem or "repro", attrs)

    def add_wait(self, seconds: float) -> None:
        """Add ``seconds`` blocked on the device to the ``wait_s``
        attribute of this thread's innermost open span (no-op with no
        span open or tracing disabled)."""
        stack = getattr(self._local, "stack", None)
        if not stack or not _state.enabled:
            return
        attrs = stack[-1].attrs
        attrs["wait_s"] = attrs.get("wait_s", 0.0) + seconds

    def waiting(self) -> _Wait:
        """``with tracer.waiting(): jax.block_until_ready(x)`` — the time
        the block takes goes to :meth:`add_wait`."""
        return _Wait(self)

    # -- read side ----------------------------------------------------------

    @property
    def total(self) -> int:
        """Spans ever recorded (not bounded by the ring capacity)."""
        return self._total

    def __len__(self) -> int:
        return len(self._spans)

    def spans(
        self, subsystem: Optional[str] = None, name: Optional[str] = None
    ) -> List[SpanRecord]:
        """In-ring spans in completion order (a child before its parent)."""
        with self._lock:
            out = list(self._spans)
        return [
            s
            for s in out
            if (subsystem is None or s.cat == subsystem)
            and (name is None or s.name == name)
        ]

    def subsystems(self) -> Dict[str, int]:
        """In-ring span counts by subsystem/category."""
        out: Dict[str, int] = {}
        for s in self.spans():
            out[s.cat] = out.get(s.cat, 0) + 1
        return out

    def export_chrome_trace(self) -> Dict:
        """The trace as a Chrome trace-event dict (Perfetto-loadable).

        ``ph: "X"`` complete events, microsecond timestamps, one lane
        per recording thread; ``args`` carry ``span_id`` and
        ``parent_id`` besides the span's attributes.
        """
        pid = os.getpid()
        events = [
            {
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": round(s.ts_us, 3),
                "dur": round(s.dur_us, 3),
                "pid": pid,
                "tid": s.tid,
                "args": {**s.args, "span_id": s.span_id, "parent_id": s.parent_id},
            }
            for s in self.spans()
        ]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path) -> str:
        """Serialize :meth:`export_chrome_trace` to ``path``; returns it."""
        payload = json.dumps(self.export_chrome_trace())
        with open(path, "w") as f:
            f.write(payload)
        return str(path)

    def reset(self) -> None:
        """Clear the ring; spans open at the time still record on exit."""
        with self._lock:
            self._spans.clear()
            self._total = 0
            self._origin = time.perf_counter()
