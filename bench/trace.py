"""Reduction of a profiler trace to device busy time, top ops and idle gaps.

A trace is read once into flat event tuples ``(plane, line, name,
start_ns, dur_ns)``: the device planes' program executions (the ``XLA
Modules`` line: one event per run of a compiled program, named by its
jitted function) and the host's ``bench.*`` annotations, which the
harness writes around each call into the system
(``jax.profiler.TraceAnnotation``).  The per-op line is not read: a
``while`` loop lists its body's ops once per iteration, millions in a
decode window, and the programs' intervals already cover every op.
Everything after loading is plain arithmetic on the tuples, so it is
checked on a small recorded fixture without a chip.

- busy: the union of program intervals on each device plane, clipped to
  the ``bench.window`` annotation, averaged over the device planes;
- top ops: device time per program name, summed over the window;
- idle gaps: the window minus the busy union, charged to the ``bench.*``
  spans that cover it (``none`` where the host was in no harness span).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int]  # plane, line, name, start_ns, dur_ns

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
MODULES_LINE = "XLA Modules"


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def profiler_options():
    """Host events at the level of user annotations only: the runtime's
    own host events would multiply the trace and its reading time."""
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def program_name(event_name: str) -> str:
    """``jit__encode_chunk(1234)`` -> ``jit__encode_chunk``."""
    return event_name.split("(", 1)[0]


def load_events(logdir: str) -> List[Event]:
    """Device program runs and host ``bench.*`` spans from the newest trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: List[Event] = []
    for plane in data.planes:
        if is_device_plane(plane.name):
            for ln in plane.lines:
                if ln.name == MODULES_LINE:
                    out.extend(
                        (plane.name, ln.name, program_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns))
                        for ev in ln.events
                    )
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.extend(
                    (plane.name, ln.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in ln.events if ev.name.startswith(SPAN_PREFIX)
                )
    return out


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def window_of(events: Sequence[Event]) -> Tuple[int, int]:
    spans = [(s, s + d) for p, _, n, s, d in events if n == WINDOW and not is_device_plane(p)]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW} annotation")
    return spans[-1]


def _clip(a: int, b: int, lo: int, hi: int) -> Optional[Tuple[int, int]]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce(events: Sequence[Event], top: int = 10) -> Dict:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps``.

    ``busy_s`` is averaged over the device planes that ran an op in the
    window; with none, it is 0 and the lists name nothing.
    """
    lo, hi = window_of(events)
    per_plane: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    op_time: Dict[str, int] = defaultdict(int)
    spans: List[Tuple[int, int, str]] = []
    for plane, _line, name, start, dur in events:
        if is_device_plane(plane):
            iv = _clip(start, start + dur, lo, hi)
            if iv:
                per_plane[plane].append(iv)
                op_time[name] += iv[1] - iv[0]
        elif name.startswith(SPAN_PREFIX) and name != WINDOW:
            spans.append((start, start + dur, name))
    unions = {p: _union(iv) for p, iv in per_plane.items()}
    busy = [sum(b - a for a, b in u) for u in unions.values()]
    busy_ns = sum(busy) / len(busy) if busy else 0.0

    spans.sort()
    starts = [a for a, _, _ in spans]
    gaps: Dict[str, int] = defaultdict(int)
    for union in unions.values():
        edges = [lo] + [t for iv in union for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                for name, t in _charge(a, b, spans, starts):
                    gaps[name] += t
    n_planes = max(len(unions), 1)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / n_planes / 1e9] for n, t in ops],
        "idle_gaps": [[n, t / n_planes / 1e9] for n, t in idle],
    }


def _charge(a: int, b: int, spans: Sequence[Tuple[int, int, str]],
            starts: Sequence[int]) -> List[Tuple[str, int]]:
    """Split the gap ``a..b`` over the harness spans it overlaps; the rest
    is ``none``.  The harness never nests its spans inside the window, so
    they are disjoint and sorted."""
    out, covered = [], 0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(spans) and spans[i][0] < b:
        lo, hi = max(a, spans[i][0]), min(b, spans[i][1])
        if hi > lo:
            out.append((spans[i][2], hi - lo))
            covered += hi - lo
        i += 1
    if b - a > covered:
        out.append(("none", b - a - covered))
    return out
