"""The program's own spans for a run's window, read from ``repro.obs``.

The served path records a root span per call into it (``serve.step`` for
a batch the engine served, ``serve.read`` for a read of the route) with
its layers nested below by parent id.  Nothing calls the engine or the
route after the window closes, so the window's roots are the last *N*
roots of that name in the tracer's ring, where *N* is what the window
counted: batches served, or reads.

A reader gets ``None``, never a partial number, where the ring cannot
give the whole window: fewer than *N* such roots in it, or spans of the
window's first root already evicted (the ring wrapped after that root
began), or a program whose spans carry no parent ids.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional


class Window(NamedTuple):
    roots: list  # the window's root spans, oldest first
    below: Dict[int, list]  # root span id -> every span of its subtree, root included


def window(root_name: str, n: float) -> Optional[Window]:
    """The last ``n`` roots named ``root_name`` and their subtrees."""
    from repro import obs

    n = int(n)
    spans = obs.tracer.spans()
    if n <= 0 or not spans or not hasattr(spans[0], "parent_id"):
        return None
    roots = [s for s in spans if s.name == root_name and s.parent_id is None][-n:]
    if len(roots) < n:
        return None
    oldest = spans[0]
    if obs.tracer.total > len(spans) and oldest.ts_us + oldest.dur_us > roots[0].ts_us:
        return None  # a span the first root may hold was evicted
    parent = {s.span_id: s.parent_id for s in spans}
    below: Dict[int, List] = {r.span_id: [] for r in roots}
    for s in spans:
        sid = s.span_id
        while sid is not None and sid not in below:
            sid = parent.get(sid)
        if sid is not None:
            below[sid].append(s)
    return Window(roots, below)


def host_ms(w: Window) -> float:
    """Mean over the roots of (root duration - every ``wait_s`` in its
    subtree), in ms: the host's own part of each call."""
    per_root = [
        r.dur_us / 1e3 - 1e3 * sum(float(s.args.get("wait_s", 0.0)) for s in w.below[r.span_id])
        for r in w.roots
    ]
    return sum(per_root) / len(per_root)


def chunks(w: Window, band_span: str) -> int:
    """Coder chunks dispatched under the window's roots (``chunks`` of
    every ``band_span``)."""
    return sum(
        int(s.args.get("chunks", 0))
        for r in w.roots for s in w.below[r.span_id] if s.name == band_span
    )
